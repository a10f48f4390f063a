"""The comparison that decides ``correct``: what the timed path produced
against the plain reference of the configuration's family.

Serving: a sample, drawn from the seed, of the greedy requests the window
finished, the longest among them. The reference reads each prompt with its
served tokens once; the number compared is the widest gap by which a
served token's logit lies below the reference's best at its position.

Training: the reference follows the program's first three steps from the
same weights and batches. Compared are each step's loss, the first
clipped gradient as the optimizer got it, and the change of the
parameters after the three steps, each leaf by its norm.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.lib import spec


def check(value: float, limit: float) -> Dict:
    return {"value": float(value), "limit": float(limit),
            "ok": bool(np.isfinite(value) and value <= limit)}


# --------------------------------------------------------------------------
# Serving
# --------------------------------------------------------------------------

def sample(tls: List, seed: int, want_tokens: int, most: int) -> List:
    """Finished greedy requests of the window: the longest, then others
    drawn from the seed until ``want_tokens`` served tokens or ``most``
    requests."""
    pool = [t for t in tls if t.counted and t.done and t.tokens
            and t.req.temperature <= 0.0]
    if not pool:
        return []
    pool.sort(key=lambda t: (len(t.req.prompt) + len(t.tokens), t.req.index))
    chosen = [pool.pop()]
    rng = np.random.default_rng([int(seed), 7])
    order = rng.permutation(len(pool))
    for i in order:
        if len(chosen) >= most or sum(len(t.tokens) for t in chosen) \
                >= want_tokens:
            break
        chosen.append(pool[i])
    return chosen


def serve_readings(cell, seed: int, chosen: List,
                   control: Optional[Dict] = None) -> Dict[str, float]:
    """Widest gap of the served tokens under the reference (``served``),
    and with ``control`` (a policy of lower precision) the widest gap of
    the tokens the reference at that precision puts first at the same
    positions (``control``)."""
    import jax
    import jax.numpy as jnp
    c = cell.config
    ref = spec.family("reference", c["family"])
    chk = c["check"]
    L, P = int(chk["pad_to"]), int(chk["positions"])
    w = jax.jit(lambda k: ref.make_weights(c, k))(ref.seed_key(seed))

    @jax.jit
    def gaps(w, toks, pos, chosen_tok, valid):
        base = ref.served_logits(c, w, toks, pos)
        out = {"served": ref.greedy_gaps(base, chosen_tok)}
        if control is not None:
            lo = ref.served_logits(c, w, toks, pos, control)
            out["control"] = ref.greedy_gaps(base, jnp.argmax(lo, axis=1))
        return {k: jnp.max(jnp.where(valid, g, -jnp.inf))
                for k, g in out.items()}

    worst: Dict[str, float] = {}
    for t in chosen:
        prompt = np.asarray(t.req.prompt, np.int32)
        served = np.asarray(t.tokens, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        if len(seq) > L or len(served) > P:
            raise ValueError(f"request of {len(seq)} tokens and "
                             f"{len(served)} served exceeds the check's "
                             f"pad_to {L} / positions {P}")
        toks = np.zeros((L,), np.int32)
        toks[:len(seq)] = seq
        pos = np.full((P,), len(prompt) - 1, np.int32)
        pos[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        tok = np.zeros((P,), np.int32)
        tok[:len(served)] = served
        valid = np.arange(P) < len(served)
        got = jax.device_get(gaps(w, toks, pos, tok, valid))
        for k, v in got.items():
            worst[k] = max(worst.get(k, -np.inf), float(v))
    return worst


def serve_checks(cell, seed: int, rec: Dict,
                 control: Optional[Dict] = None) -> Dict:
    """``served_logit_gap`` against the configuration's limit. With
    ``control`` (a policy of lower precision) the reference at that
    precision is put in the program's place: the number compared is the
    gap of the tokens it puts first, and the served tokens' gap is kept
    beside it in ``rec["readings"]``."""
    chk = cell.config["check"]
    chosen = sample(rec["timelines"], seed, int(chk["tokens"]),
                    int(chk["requests"]))
    rec["check_sample"] = {"requests": len(chosen),
                           "tokens": sum(len(t.tokens) for t in chosen)}
    if not chosen:
        return {"served_logit_gap": check(np.inf, chk["limit"])}
    r = serve_readings(cell, seed, chosen, control)
    rec["readings"] = r
    value = r["control"] if control is not None else r["served"]
    return {"served_logit_gap": check(value, chk["limit"])}


# --------------------------------------------------------------------------
# Training
# --------------------------------------------------------------------------

def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              skip=()) -> Dict[str, float]:
    """Per leaf: |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    names = [k for k in ref if k not in skip]
    med = float(np.median([ref[k] for k in names]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in names}


def train_checks(cell, prog: Dict, ref: Dict) -> Dict:
    """``prog`` and ``ref``: {"loss": [...], "grad_norm": {leaf: norm},
    "change_norm": {leaf: norm}} under the reference's leaf names."""
    lim = cell.config["check"]
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss_step{i + 1}_rel"] = check(abs(a - b) / abs(b),
                                             lim["loss_rel"])
    g = leaf_gaps(prog["grad_norm"], ref["grad_norm"])
    worst = max(g, key=g.get)
    out["grad_norm_worst_leaf"] = check(g[worst], lim["grad_norm"])
    # leaves the reference's first gradient leaves at rounding (a key's
    # bias under softmax) move under Adam by round-off alone
    med = float(np.median(list(ref["grad_norm"].values())))
    frozen = [k for k, v in ref["grad_norm"].items() if v < 1e-3 * med]
    c = leaf_gaps(prog["change_norm"], ref["change_norm"], skip=frozen)
    worst_c = max(c, key=c.get)
    out["change_norm_worst_leaf"] = check(c[worst_c], lim["change_norm"])
    return out, {"grad_worst": worst, "change_worst": worst_c,
                 "skipped": frozen, "grad_gaps": g, "change_gaps": c}
