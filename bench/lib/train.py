"""Training cells: the program's QAT step (``launch.steps.make_train_step``
under its ``TrainConfig``) run back to back on batches from the program's
synthetic data pipeline, generated from ``--seed`` inside the window.

Set-up builds the one compiled step with its state, and drives it through
its first three steps with the window's own call and feed; the reference
follows those three steps after the window. The window then runs steps
until the first to finish at or after ``--seconds``; the rate is over all
of them and all of their time.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from bench.lib import correct, spec
from bench.lib import trace as reduce
from bench.lib.harness import log, memory_peak_bytes

CHECK_STEPS = 3


def _named_norms(tree, ad, scale: float = 1.0) -> Dict[str, float]:
    """Per-leaf L2 norms under the reference's leaf names."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32)))) for _, leaf in flat])
    out = {}
    for (path, _), n in zip(flat, norms):
        name = ad.neutral_name(_dotted(path))
        if name is not None:
            out[name] = float(n) * scale
    return out


def _dotted(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return ".".join(parts)


def _change_norms(p0: Dict, p1, ad) -> Dict[str, float]:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(p1)
    host = jax.device_get([leaf for _, leaf in flat])
    out = {}
    for (path, _), a in zip(flat, host):
        key = _dotted(path)
        name = ad.neutral_name(key)
        if name is not None:
            d = np.asarray(a, np.float32) - p0[key]
            out[name] = float(np.linalg.norm(d.ravel()))
    return out


def build(cell, seed: int):
    """Teacher weights from the seed in one program; the student is the
    program's calibration of a copy; AdamW state; the compiled step."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.data import MixtureIterator, SyntheticConfig
    from repro.launch.steps import make_train_step
    from repro.launch.train import calibrate
    from repro.optim import adamw_init
    c, mix = cell.config, cell.traffic
    ref = spec.family("reference", c["family"])
    ad = spec.family("adapters", c["family"])
    cfg = ad.model_config(c)
    tcfg = TrainConfig(**c["train"], batch_size=mix["batch_size"],
                       seq_len=mix["seq_len"], dclm_ratio=mix["dclm_ratio"],
                       seed=seed)
    data = SyntheticConfig(vocab_size=cfg.vocab_size, seq_len=mix["seq_len"],
                           batch_size=mix["batch_size"],
                           dclm_ratio=mix["dclm_ratio"], seed=seed)
    teacher = jax.jit(lambda k: ad.program_params(
        c, ref.make_weights(c, k)))(ref.seed_key(seed))
    student = calibrate(cfg, jax.tree.map(jnp.copy, teacher), tcfg, data)
    opt = adamw_init(student)
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 2))
    it = MixtureIterator(data)
    return cfg, tcfg, teacher, student, opt, step, it


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, counter, root) -> Dict:
    import jax
    import jax.numpy as jnp
    c, mix = cell.config, cell.traffic
    ad = spec.family("adapters", c["family"])
    ref = spec.family("reference", c["family"])
    cfg, tcfg, teacher, student, opt, step, it = build(cell, seed)
    tokens_per_step = mix["batch_size"] * mix["seq_len"]

    def feed():
        return {k: jnp.asarray(v) for k, v in next(it).items()}

    # the first steps, through the window's call and feed
    flat0, _ = jax.tree_util.tree_flatten_with_path(student)
    p0 = {_dotted(p): np.asarray(a, np.float32)
          for (p, _), a in zip(flat0, jax.device_get(
              [leaf for _, leaf in flat0]))}
    batches, prog = [], {"loss": []}
    for i in range(CHECK_STEPS):
        hb = next(it)
        batches.append(hb)
        student, opt, m = step(student, teacher, opt,
                               {k: jnp.asarray(v) for k, v in hb.items()},
                               jnp.int32(i))
        prog["loss"].append(float(m["loss"]))
        if i == 0:
            # Adam's first moment after one step is (1 - beta1) * g
            prog["grad_norm"] = _named_norms(opt.m, ad,
                                             1.0 / (1.0 - tcfg.beta1))
    prog["change_norm"] = _change_norms(p0, student, ad)
    del p0

    prof = reduce.Window(root, trace, mix.get("trace_seconds", 1e9))
    traced_steps = 0
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    counter.armed = True
    prof.start(w0)
    done, losses, pending, i = 0, [], None, CHECK_STEPS
    t_last = w0
    span = jax.profiler.TraceAnnotation
    while True:
        with span("bench.feed"):
            b = feed()
        with span("bench.dispatch_step"):
            student, opt, m = step(student, teacher, opt, b, jnp.int32(i))
        i += 1
        if pending is not None:
            losses.append(pending)
            with span("bench.wait_step"):
                pending.block_until_ready()
            t_last = time.perf_counter()
            done += 1
            if prof.maybe_stop(t_last):
                traced_steps = done
            if t_last - w0 >= seconds:
                break
        pending = m["loss"]
    counter.armed = False
    jax.block_until_ready(student)
    prof.stop()
    losses = np.asarray(jax.device_get(losses), np.float64)
    rec = {"config": c, "traffic": mix, "setup_s": setup_s,
           "window": (w0, t_last),
           "window_compiles": counter.count,
           "memory_peak_bytes": memory_peak_bytes(cell.chips),
           "steps": done, "tokens_per_step": tokens_per_step,
           "attempted": done, "failed": int(np.sum(~np.isfinite(losses))),
           "e2e": {"train_tok_s": done * tokens_per_step / (t_last - w0)}}
    rec.update(prof.record())
    rec["traced_steps"] = traced_steps or done
    log(f"bench: {done} steps in {t_last - w0:.3f} s, losses "
        f"{prog['loss']}, e2e {rec['e2e']}")
    del student, teacher, opt, step, m, pending
    gc.collect()
    jax.clear_caches()
    w = jax.jit(lambda k: ref.make_weights(c, k))(ref.seed_key(seed))
    want = ref.qat_readings(c, c["train"], w, batches)
    rec["readings"] = {"program": prog, "reference": want}
    rec["checks"], rec["check_detail"] = correct.train_checks(cell, prog,
                                                              want)
    return rec
