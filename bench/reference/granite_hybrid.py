"""Plain float32 reference for the Granite 4.0-H hybrid (hf
``GraniteMoeHybridForCausalLM`` without experts) under the SiLQ quantizers.

The decoder: token embeddings times ``embedding_multiplier``; layers of
RMSNorm -> mixer -> residual add of the mixer's output times
``residual_multiplier`` -> RMSNorm -> SwiGLU MLP -> residual add of the
MLP's output times ``residual_multiplier``; a final RMSNorm; the tied head,
its logits divided by ``logits_scaling``. The mixer is, by ``layer_types``:

* ``attention``: grouped-query attention with no position embedding
  (``position_embedding_type`` "nope") and softmax scale
  ``attention_multiplier``;
* ``mamba`` (Mamba-2, one group): ``in_proj`` gives z, x|B|C and dt; a
  causal depthwise conv of width ``mamba_d_conv`` with bias over x|B|C,
  then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD in its
  quadratic masked form over the whole sequence,
  y = ((C B^T) o L)(x dt) + D x with L[t, s] = exp(sum_{s<r<=t} A dt_r);
  a gated RMSNorm of y * silu(z); ``out_proj``.

The quantizers are the SiLQ paper's, as in ``bench/reference/qwen2.py``
(token-dynamic A8 inputs to every linear, W4 per-channel LSQ weights, the
query at 16 and K/V at ``cache_bits`` per token, the head at 8/8). The
conv, dt, A_log, D, the SSD and the gated norm carry no quantizer.

Departures from the published model, on both sides of the comparison: the
weights are random from a seed; only the first ``num_hidden_layers`` of
``layer_types`` are kept. Departure from the paper's optimizer, on both
sides: weight decay applies to every weight that is not a step size or the
final norm gain, as the program's ``optim.adamw._decay_mask`` has it (its
layer-stacked norm gains, conv biases, A_log, D and dt_bias are 2-D); the
paper decays matrix weights only, and Mamba-2 exempts A_log and D.

This module imports nothing of the program under test.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen2 import (attention, dyn_quant_ste, kd_loss, lr_at,
                                   lsq_quant, mse_scale, rms_norm)
# the harness asks each family's reference for its seed_key
from bench.reference.qwen2 import seed_key  # noqa: F401

ATTN_LINEARS = ("wq", "wk", "wv", "wo")
MAMBA_LINEARS = ("in_proj", "out_proj")
MLP_LINEARS = ("wg", "wu", "wd")
LINEARS = ATTN_LINEARS + MAMBA_LINEARS + MLP_LINEARS
F32_LEAVES = ("A_log", "D", "dt_bias")


# --------------------------------------------------------------------------
# Sizes and weights
# --------------------------------------------------------------------------

def dims(c: Dict) -> Dict:
    """The sizes the reference needs, from a config file's keys."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    L = c["num_hidden_layers"]
    Hs, P = c["mamba_n_heads"], c["mamba_d_head"]
    assert Hs * P == c["mamba_expand"] * d, "mamba heads x d_head != inner"
    assert c["mamba_n_groups"] == 1 and c["position_embedding_type"] == "nope"
    assert c["mamba_conv_bias"] and not c["mamba_proj_bias"]
    assert not c["attention_bias"] and c["tie_word_embeddings"]
    return {"d": d, "H": H, "Hkv": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // H,
            "f": c["shared_intermediate_size"], "L": L,
            "V": c["vocab_size"], "eps": float(c["rms_norm_eps"]),
            "kinds": tuple(c["layer_types"][:L]),
            "Hs": Hs, "P": P, "N": c["mamba_d_state"],
            "K": c["mamba_d_conv"], "chunk": c["mamba_chunk_size"],
            "emb_mult": float(c["embedding_multiplier"]),
            "res_mult": float(c["residual_multiplier"]),
            "attn_mult": float(c["attention_multiplier"]),
            "logits_scaling": float(c["logits_scaling"])}


def layer_shapes(m: Dict, kind: str) -> Dict[str, tuple]:
    """One layer's tensors, linears as (d_in, d_out)."""
    d, f = m["d"], m["f"]
    s = {"ln1": (d,), "ln2": (d,), "wg": (d, f), "wu": (d, f), "wd": (f, d)}
    if kind == "attention":
        qd, kvd = m["H"] * m["hd"], m["Hkv"] * m["hd"]
        s.update(wq=(d, qd), wk=(d, kvd), wv=(d, kvd), wo=(qd, d))
    else:
        inner, Hs = m["Hs"] * m["P"], m["Hs"]
        conv_ch = inner + 2 * m["N"]
        s.update(in_proj=(d, inner + conv_ch + Hs), out_proj=(inner, d),
                 conv_w=(m["K"], conv_ch), conv_b=(conv_ch,), A_log=(Hs,),
                 D=(Hs,), dt_bias=(Hs,), norm=(inner,))
    return s


def weight_shapes(c: Dict) -> Dict[str, tuple]:
    """Neutral layout: ``embed``, ``final_norm`` and ``<layer>.<tensor>``
    for each layer."""
    m = dims(c)
    s = {"embed": (m["V"], m["d"]), "final_norm": (m["d"],)}
    for i, kind in enumerate(m["kinds"]):
        s.update({f"{i}.{n}": sh for n, sh in layer_shapes(m, kind).items()})
    return s


def _leaf(name: str) -> str:
    return name.split(".")[-1]


def make_weights(c: Dict, key) -> Dict[str, jnp.ndarray]:
    """Random weights, bf16 but for A_log, D and dt_bias (f32). Linears
    ~ N(0, 1/d_in); the embedding N(0, 0.02^2); norm gains 1 + N(0,
    0.05^2); the conv's weights and bias U(-1/sqrt(K), 1/sqrt(K)) (the
    framework default of a depthwise conv); A_log = log U(1, 16), D = 1,
    dt_bias the inverse softplus of a dt log-uniform in [1e-3, 0.1] (the
    Mamba-2 initialisation). Each tensor has its own fold of ``key``."""
    m = dims(c)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(c).items())):
        k = jax.random.fold_in(key, i)
        leaf = _leaf(name)
        if leaf in ("conv_w", "conv_b"):
            b = m["K"] ** -0.5
            w = jax.random.uniform(k, shape, jnp.float32, -b, b)
        elif leaf == "A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "D":
            w = jnp.ones(shape, jnp.float32)
        elif leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            np.log(1e-3), np.log(0.1)))
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:
            z = jax.random.normal(k, shape, jnp.float32)
            if leaf in ("ln1", "ln2", "norm", "final_norm"):
                w = 1.0 + 0.05 * z
            elif leaf == "embed":
                w = 0.02 * z
            else:
                w = z * shape[-2] ** -0.5
        out[name] = w if leaf in F32_LEAVES else w.astype(jnp.bfloat16)
    return out


# --------------------------------------------------------------------------
# Forward (float32)
# --------------------------------------------------------------------------

def ssd_quadratic(x, dt, A, B, C):
    """y[t] = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} A dt_r) dt_s x_s, over
    the whole sequence at once. x (S, H, P), dt (S, H), A (H,), B/C (S, N).
    The segment sums are summed directly (not differenced from one long
    cumulative sum), so they carry no cancellation."""
    S = x.shape[0]
    a = (dt * A).T                                        # (H, S)
    below = jnp.tril(jnp.ones((S, S), bool), -1)          # r > s
    seg = jnp.cumsum(jnp.where(below, a[:, :, None], 0.0), axis=1)
    causal = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))     # (H, t, s)
    w = (C @ B.T)[None] * decay
    return jnp.einsum("hts,shp->thp", w, x * dt[..., None])


def _mamba(m, lw, h, lin):
    S = h.shape[0]
    inner, N, Hs, K = m["Hs"] * m["P"], m["N"], m["Hs"], m["K"]
    zxbcdt = lin(h, "in_proj")
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * N],
                  zxbcdt[:, 2 * inner + 2 * N:])
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = lw["conv_b"] + sum(lw["conv_w"][j] * xp[j:j + S]
                              for j in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(S, Hs, m["P"])
    B, C = xbc[:, inner:inner + N], xbc[:, inner + N:]
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    A = -jnp.exp(lw["A_log"])
    y = ssd_quadratic(x, dt, A, B, C) + lw["D"][:, None] * x
    g = y.reshape(S, inner) * jax.nn.silu(z)
    return lin(rms_norm(g, lw["norm"], m["eps"]), "out_proj")


def _attention(m, h, lin, cache_bits):
    S = h.shape[0]
    q = lin(h, "wq").reshape(S, m["H"], m["hd"])
    k = lin(h, "wk").reshape(S, m["Hkv"], m["hd"])
    v = lin(h, "wv").reshape(S, m["Hkv"], m["hd"])
    # ``attention`` scales scores by 1/sqrt(hd): give q the rest of the
    # configuration's score scale
    q = q * (m["attn_mult"] * m["hd"] ** 0.5)
    if cache_bits < 16:
        q = dyn_quant_ste(q, 16)
        k = dyn_quant_ste(k, cache_bits)
        v = dyn_quant_ste(v, cache_bits)
    return lin(attention(q, k, v).reshape(S, -1), "wo")


def _layer(m, kind, lw, x, qfn_w, qfn_x, cache_bits):
    def lin(h, name):
        return qfn_x(h) @ qfn_w(name)

    r = m["res_mult"]
    h = rms_norm(x, lw["ln1"], m["eps"])
    y = _attention(m, h, lin, cache_bits) if kind == "attention" \
        else _mamba(m, lw, h, lin)
    x = x + r * y
    h = rms_norm(x, lw["ln2"], m["eps"])
    return x + r * lin(jax.nn.silu(lin(h, "wg")) * lin(h, "wu"), "wd")


def train_logits(c: Dict, p: Dict, tokens, policy=None) -> jnp.ndarray:
    """(S, V) logits of the training forward: fake-quant under ``policy``,
    or unquantized (the teacher) when ``policy`` is None. Each layer's
    weights are upcast inside it, and it is recomputed in the backward
    (``jax.checkpoint``), so the quadratic SSD holds one layer at a time."""
    m = dims(c)
    f32 = jnp.float32
    x = p["embed"][tokens].astype(f32) * m["emb_mult"]
    for i, kind in enumerate(m["kinds"]):
        lw = {_leaf(n): v for n, v in p.items()
              if n.startswith(f"{i}.") and not _leaf(n).startswith("s_")}
        ls = {} if policy is None else \
            {n: p[f"{i}.s_{n}"] for n in lw if n in LINEARS}

        def layer(x, lw, ls, kind=kind):
            lw = {k: v.astype(f32) for k, v in lw.items()}
            if policy is None:
                return _layer(m, kind, lw, x, lambda n: lw[n],
                              lambda h: h, 16)
            return _layer(
                m, kind, lw, x,
                lambda n: lsq_quant(lw[n], ls[n], policy["weight_bits"]),
                lambda h: dyn_quant_ste(h, policy["act_bits"]),
                policy["cache_bits"])

        x = jax.checkpoint(layer)(x, lw, ls)
    x = rms_norm(x, p["final_norm"].astype(f32), m["eps"])
    hw = p["embed"].astype(f32).T
    if policy is None:
        logits = x @ hw
    else:
        logits = (dyn_quant_ste(x, policy["head_bits"])
                  @ lsq_quant(hw, p["s_head"], policy["head_bits"]))
    return logits / m["logits_scaling"]


# --------------------------------------------------------------------------
# Training: the QAT step (KD from the unquantized teacher, AdamW, LSQ)
# --------------------------------------------------------------------------

QAT_POLICY = {"act_bits": 8, "cache_bits": 8, "weight_bits": 4,
              "head_bits": 8}


def student_scales(w: Dict, policy: Dict = QAT_POLICY) -> Dict:
    """Calibrated weight step sizes: the convex MSE rule (paper Eq. 2)."""
    s = {}
    for n, v in w.items():
        if _leaf(n) in LINEARS:
            i = n.split(".")[0]
            s[f"{i}.s_{_leaf(n)}"] = mse_scale(v, policy["weight_bits"])
    s["s_head"] = mse_scale(w["embed"].T, policy["head_bits"])
    return s


def decays(name: str) -> bool:
    """As the program's ``_decay_mask``: every weight but the step sizes
    and the final norm gain (see the module's departures)."""
    return not _leaf(name).startswith("s_") and name != "final_norm"


def make_train_step(c: Dict, t: Dict, policy: Dict = QAT_POLICY):
    """One QAT step: (params, teacher, m, v, tokens, mask, step, lr) ->
    (params, m, v, loss, clipped grads' per-leaf norms). The gradient is
    taken through the stored weights (bf16, and f32 for step sizes, A_log,
    D, dt_bias) and each leaf is updated in float32 and stored back in its
    own dtype; parameters and moments are donated, so the step holds about
    14 bytes a parameter besides one layer's activations."""
    b1, b2, eps = t["beta1"], t["beta2"], t["eps"]

    def step_fn(p, teacher, mom, vel, tokens, mask, step, lr):
        with jax.default_matmul_precision("highest"):
            t_logits = train_logits(c, teacher, tokens)

            def loss_fn(p):
                return kd_loss(train_logits(c, p, tokens, policy),
                               t_logits, mask)

            loss, g = jax.value_and_grad(loss_fn)(p)
        g = {k: x.astype(jnp.float32) for k, x in g.items()}
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        clip = t["grad_clip"]
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)) if clip \
            else 1.0
        n = (step + 1).astype(jnp.float32)
        new_p, new_m, new_v, norms = {}, {}, {}, {}
        for k in p:
            gk = g[k] * scale
            norms[k] = jnp.sqrt(jnp.sum(gk * gk))
            mk = b1 * mom[k] + (1 - b1) * gk
            vk = b2 * vel[k] + (1 - b2) * gk ** 2
            upd = (mk / (1 - b1 ** n)) / (jnp.sqrt(vk / (1 - b2 ** n)) + eps)
            pk = p[k].astype(jnp.float32)
            if decays(k):
                upd = upd + t["weight_decay"] * pk
            new_p[k] = (pk - lr * upd).astype(p[k].dtype)
            new_m[k], new_v[k] = mk, vk
        return new_p, new_m, new_v, loss, norms

    return jax.jit(step_fn, donate_argnums=(0, 2, 3))


def qat_readings(c: Dict, t: Dict, w: Dict, batches: Sequence[Dict],
                 policy: Dict = QAT_POLICY) -> Dict:
    """Run the reference over ``batches`` from weights ``w``: the loss of
    every step, the per-leaf norms of the first step's clipped gradient,
    and the per-leaf norms of the change over all steps."""
    with jax.default_matmul_precision("highest"):
        scales = jax.jit(lambda w: student_scales(w, policy))(w)
    p = {**jax.tree.map(jnp.copy, w), **scales}    # donated: not the teacher
    p0_host = {k: np.asarray(v, np.float32) for k, v in p.items()}
    mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    vel = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    step_fn = make_train_step(c, t, policy)
    losses: List[float] = []
    grad_norms = None
    for i, b in enumerate(batches):
        p, mom, vel, loss, norms = step_fn(
            p, w, mom, vel, jnp.asarray(b["tokens"][0]),
            jnp.asarray(b["loss_mask"][0], jnp.float32), jnp.int32(i),
            jnp.float32(lr_at(i, t)))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
    change = {k: float(np.linalg.norm(
        np.asarray(v, np.float32).ravel() - p0_host[k].ravel()))
        for k, v in p.items()}
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
