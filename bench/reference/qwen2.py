"""Plain float32 reference for the Qwen2 family under the SiLQ quantizers.

Qwen2 / Qwen2.5 decoder (arXiv:2407.10671, hf ``Qwen2ForCausalLM``):
RMSNorm pre-norm blocks, grouped-query attention with a bias on the q, k
and v projections, rotary embeddings (rotate-half convention), a SwiGLU
MLP, and a tied or untied vocabulary head.

The quantizers are the SiLQ paper's (arXiv:2507.16933), applied in float32
under ``jax.default_matmul_precision("highest")``:

* every linear's input: token-dynamic symmetric integers at ``act_bits``
  (scale = absmax over the features / q_max);
* every body weight: symmetric ``weight_bits`` integers, one step size per
  output channel;
* the query into QK^T: token-dynamic 16-bit per head; K and V as stored in
  the cache: token-dynamic ``cache_bits`` per head;
* the head: token-dynamic 8-bit input, weights at 8 bits in training; the
  deployed head weights are re-gridded onto the int4 lattice
  (``s4 = s8 * 127 / 7``), which is what a served A8d-C8-W4 model computes.

Departures from the published model, on both sides of the comparison: the
weights are random from a seed, not the released checkpoint, and the
norm and bias values are drawn near their trained scale.

This module imports nothing of the program under test. ``make_weights``
is the one source of weights: the harness hands them to the program
through its own adapter, and this module reads them from the seed again.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-9


# --------------------------------------------------------------------------
# Sizes and weights
# --------------------------------------------------------------------------

def dims(c: Dict) -> Dict[str, int]:
    """The sizes the reference needs, from a config file's keys."""
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    hd = c.get("head_dim") or d // H
    return {"d": d, "H": H, "Hkv": c["num_key_value_heads"], "hd": hd,
            "f": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"], "tied": bool(c["tie_word_embeddings"]),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def weight_shapes(c: Dict) -> Dict[str, tuple]:
    """Neutral layout: per-layer tensors stacked on a leading layer axis,
    linears as (d_in, d_out)."""
    m = dims(c)
    d, L, f, V = m["d"], m["L"], m["f"], m["V"]
    qd, kvd = m["H"] * m["hd"], m["Hkv"] * m["hd"]
    s = {"embed": (V, d), "final_norm": (d,),
         "ln1": (L, d), "ln2": (L, d),
         "wq": (L, d, qd), "bq": (L, qd), "wk": (L, d, kvd), "bk": (L, kvd),
         "wv": (L, d, kvd), "bv": (L, kvd), "wo": (L, qd, d),
         "wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)}
    if not m["tied"]:
        s["head"] = (d, V)
    return s


LINEARS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_weights(c: Dict, key) -> Dict[str, jnp.ndarray]:
    """Random bf16 weights. Linears ~ N(0, 1/d_in), embedding and head
    N(0, 0.02^2) and N(0, 1/d), biases N(0, 0.02^2), norm gains
    1 + N(0, 0.05^2). Each tensor has its own fold of ``key``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(c).items())):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("ln1", "ln2", "final_norm"):
            w = 1.0 + 0.05 * z
        elif name == "embed" or name.startswith("b"):
            w = 0.02 * z
        else:                               # linears and the untied head
            w = z * shape[-2] ** -0.5
        out[name] = w.astype(jnp.bfloat16)
    return out


# --------------------------------------------------------------------------
# Quantizers (float32)
# --------------------------------------------------------------------------

def qmax(bits: int) -> int:
    return 2 ** (bits - 1) - 1


def dyn_quant(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Token-dynamic symmetric quant-dequant over the last axis."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax(bits),
                    EPS)
    return jnp.round(x / s) * s


def weight_quant(w: jnp.ndarray, s: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Per-output-channel symmetric quant-dequant, clipped to the grid."""
    s = jnp.maximum(s, EPS)
    return jnp.round(jnp.clip(w / s, -qmax(bits) - 1, qmax(bits))) * s


def lsq_init_scale(w: jnp.ndarray, bits: int) -> jnp.ndarray:
    """LSQ initialization per output channel: 2 mean|w| / sqrt(q_max),
    shape (..., 1, d_out) for a (..., d_in, d_out) weight."""
    m = jnp.mean(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    return jnp.maximum(2.0 * m / math.sqrt(qmax(bits)), EPS)


def mse_scale(w: jnp.ndarray, bits: int, iters: int = 64) -> jnp.ndarray:
    """The paper's convex MSE objective (Eq. 2) per output channel,
    minimized by ternary search on (0, max|w| / b], b = 2^(p-1) - 1/2."""
    a = jnp.swapaxes(jnp.abs(w.astype(jnp.float32)), -1, -2)  # (.., out, in)
    b = 2.0 ** (bits - 1) - 0.5

    def obj(s):
        over = jnp.maximum(a - s[..., None] * b, 0.0)
        return jnp.sum(jnp.maximum(s[..., None] ** 2 / 12.0, over ** 2), -1)

    def body(_, br):
        lo, hi = br
        m1, m2 = lo + (hi - lo) / 3.0, hi - (hi - lo) / 3.0
        left = obj(m1) > obj(m2)
        return jnp.where(left, m1, lo), jnp.where(left, hi, m2)

    hi = jnp.maximum(jnp.max(a, -1) / b, 1e-8)
    lo, hi = jax.lax.fori_loop(0, iters, body, (jnp.full_like(hi, 1e-9), hi))
    return ((lo + hi) / 2.0)[..., None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def lsq_quant(w, s, bits: int):
    """Weight quant-dequant with LSQ gradients (training)."""
    return weight_quant(w, s, bits)


def _lsq_fwd(w, s, bits):
    return weight_quant(w, s, bits), (w, s)


def _lsq_bwd(bits, res, g):
    """LSQ (Esser et al., 2019): straight-through inside the grid, zero
    outside; the step size's gradient scaled by 1/sqrt(n * q_max)."""
    w, s = res
    lo, hi = -qmax(bits) - 1, qmax(bits)
    v = w / jnp.maximum(s, EPS)
    inside = (v >= lo) & (v <= hi)
    dq = jnp.where(inside, jnp.round(v) - v, jnp.clip(v, lo, hi))
    axes = tuple(i for i in range(w.ndim) if s.shape[i] == 1)
    ds = jnp.sum(g * dq, axis=axes, keepdims=True)
    return jnp.where(inside, g, 0.0), ds / math.sqrt(w.size // s.size * hi)


lsq_quant.defvjp(_lsq_fwd, _lsq_bwd)


def dyn_quant_ste(x, bits: int):
    """Token-dynamic quant-dequant whose gradient is the identity."""
    return x + jax.lax.stop_gradient(dyn_quant(x, bits) - x)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x (S, heads, hd), rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, q_chunk: int = 512):
    """Causal softmax attention, q (S, H, hd), k/v (S, Hkv, hd), in query
    chunks so the score block stays small at long sequences."""
    S, H, hd = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    n = -(-S // q_chunk)
    qp = jnp.pad(q, ((0, n * q_chunk - S), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * q_chunk, q_chunk, 0)
        sc = jnp.einsum("qhd,khd->hqk", qi, k) * hd ** -0.5
        qpos = i * q_chunk + jnp.arange(q_chunk)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(one, jnp.arange(n))
    return out.reshape(n * q_chunk, H, hd)[:S]


def _layer(m, lw, x, pos, qfn_w, qfn_x, cache_bits, query_bits=16):
    """One decoder layer; ``qfn_w(name)`` gives the quantized weight,
    ``qfn_x(x)`` the quantized linear input."""
    S = x.shape[0]

    def lin(h, name, bias=None):
        y = qfn_x(h) @ qfn_w(name)
        return y if bias is None else y + lw[bias].astype(jnp.float32)

    h = rms_norm(x, lw["ln1"].astype(jnp.float32), m["eps"])
    q = lin(h, "wq", "bq").reshape(S, m["H"], m["hd"])
    k = lin(h, "wk", "bk").reshape(S, m["Hkv"], m["hd"])
    v = lin(h, "wv", "bv").reshape(S, m["Hkv"], m["hd"])
    q = rope(q, pos, m["theta"])
    k = rope(k, pos, m["theta"])
    if cache_bits < 16:
        q = dyn_quant_ste(q, query_bits)
        k = dyn_quant_ste(k, cache_bits)
        v = dyn_quant_ste(v, cache_bits)
    a = attention(q, k, v).reshape(S, m["H"] * m["hd"])
    x = x + lin(a, "wo")
    h = rms_norm(x, lw["ln2"].astype(jnp.float32), m["eps"])
    g, u = lin(h, "wg"), lin(h, "wu")
    return x + lin(jax.nn.silu(g) * u, "wd")


def head_weight(w) -> jnp.ndarray:
    """(d, V) head weight: the untied head or the transposed embedding."""
    return w["head"] if "head" in w else w["embed"].T


# --------------------------------------------------------------------------
# Serving: logits of a served sequence
# --------------------------------------------------------------------------

def serve_scales(w: Dict, weight_bits: int = 4) -> Dict[str, jnp.ndarray]:
    """The served checkpoint's weight step sizes: LSQ initialization per
    output channel, body at ``weight_bits``, head at 8 bits."""
    s = {n: lsq_init_scale(w[n], weight_bits) for n in LINEARS}
    s["head"] = lsq_init_scale(head_weight(w), 8)
    return s


SERVED = {"act_bits": 8, "cache_bits": 8, "weight_bits": 4}


def served_logits(c: Dict, w: Dict, tokens: jnp.ndarray, positions,
                  policy: Dict = SERVED) -> jnp.ndarray:
    """Float32 logits (len(positions), V) of a served sequence ``tokens``
    at ``positions``, under ``policy`` (act, cache and weight bits). The
    head is served at int4 on the re-gridded 8-bit step size, with
    ``act_bits`` inputs (8 in the served policy)."""
    m = dims(c)
    wb, ab = policy["weight_bits"], policy["act_bits"]
    scales = serve_scales(w, wb)
    layers = {n: w[n] for n in ("ln1", "ln2", "bq", "bk", "bv") + LINEARS}
    lscales = {n: scales[n] for n in LINEARS}
    pos = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens].astype(jnp.float32)

        def body(x, inp):
            lw, ls = inp
            x = _layer(m, lw, x, pos,
                       lambda n: weight_quant(lw[n].astype(jnp.float32),
                                              ls[n], wb),
                       lambda h: dyn_quant(h, ab), policy["cache_bits"])
            return x, None

        x, _ = jax.lax.scan(body, x, (layers, lscales))
        x = rms_norm(x[positions], w["final_norm"].astype(jnp.float32),
                     m["eps"])
        s4 = scales["head"] * (qmax(8) / qmax(4))
        hw = weight_quant(head_weight(w).astype(jnp.float32), s4, 4)
        return dyn_quant(x, ab) @ hw


def greedy_gaps(logits: jnp.ndarray, chosen: jnp.ndarray) -> jnp.ndarray:
    """How far each chosen token's logit lies below the row's best."""
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - picked


# --------------------------------------------------------------------------
# Training: the QAT step (KD from the unquantized teacher, AdamW, LSQ)
# --------------------------------------------------------------------------

QAT_POLICY = {"act_bits": 8, "cache_bits": 8, "weight_bits": 4,
              "head_bits": 8}


def student_scales(w: Dict, policy: Dict = QAT_POLICY) -> Dict:
    """Calibrated weight step sizes: the convex MSE rule (paper Eq. 2)."""
    s = {"s_" + n: mse_scale(w[n], policy["weight_bits"]) for n in LINEARS}
    s["s_head"] = mse_scale(head_weight(w), policy["head_bits"])
    return s


def train_logits(c: Dict, p: Dict, tokens, policy=None) -> jnp.ndarray:
    """(S, V) logits of the training forward: fake-quant under ``policy``,
    or unquantized (the teacher) when ``policy`` is None."""
    m = dims(c)
    L = m["L"]
    pos = jnp.arange(tokens.shape[0])
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    x = f32["embed"][tokens]
    names = ("ln1", "ln2", "bq", "bk", "bv") + LINEARS
    for li in range(L):
        lw = {n: f32[n][li] for n in names}
        if policy is None:
            qw, qx, cb = (lambda n, lw=lw: lw[n]), (lambda h: h), 16
        else:
            qw = (lambda n, lw=lw, li=li:
                  lsq_quant(lw[n], f32["s_" + n][li], policy["weight_bits"]))
            qx = (lambda h: dyn_quant_ste(h, policy["act_bits"]))
            cb = policy["cache_bits"]
        x = _layer(m, lw, x, pos, qw, qx, cb)
    x = rms_norm(x, f32["final_norm"], m["eps"])
    hw = f32["head"] if "head" in f32 else f32["embed"].T
    if policy is None:
        return x @ hw
    return (dyn_quant_ste(x, policy["head_bits"])
            @ lsq_quant(hw, f32["s_head"], policy["head_bits"]))


def kd_loss(student_logits, teacher_logits, mask):
    """Soft cross-entropy against the teacher at temperature 1, masked
    mean over tokens."""
    ce = -jnp.sum(jax.nn.softmax(teacher_logits, -1)
                  * jax.nn.log_softmax(student_logits, -1), -1)
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def is_scale(name: str) -> bool:
    return name.startswith("s_")


def decays(name: str, shape) -> bool:
    """Weight decay on matrix weights only: never on step sizes, norm
    gains or biases (paper, Appendix B)."""
    return not is_scale(name) and name not in (
        "ln1", "ln2", "final_norm", "bq", "bk", "bv") and len(shape) >= 2


def lr_at(step: int, t: Dict) -> float:
    """Cosine to ``min_lr_ratio`` over ``total_steps`` (no warm-up), base
    rate rescaled by sqrt(ref_steps / total_steps)."""
    base = t["learning_rate"] * (t["ref_steps"] / t["total_steps"]) ** 0.5
    prog = min(max(step / max(t["total_steps"], 1), 0.0), 1.0)
    r = t["min_lr_ratio"]
    return base * (r + (1.0 - r) * 0.5 * (1.0 + math.cos(math.pi * prog)))


def make_train_step(c: Dict, t: Dict, policy: Dict = QAT_POLICY):
    """One QAT step: (params, teacher, m, v, tokens, labels, mask, step,
    lr) -> (params, m, v, loss, clipped grads' per-leaf norms). Weights
    are stored in bf16 and updated in float32, step sizes in float32."""
    b1, b2, eps = t["beta1"], t["beta2"], t["eps"]

    def step_fn(p, teacher, mom, vel, tokens, mask, step, lr):
        with jax.default_matmul_precision("highest"):
            t_logits = train_logits(c, teacher, tokens)

            def loss_fn(pf):
                return kd_loss(train_logits(c, pf, tokens, policy),
                               t_logits, mask)

            pf = {k: v.astype(jnp.float32) for k, v in p.items()}
            loss, g = jax.value_and_grad(loss_fn)(pf)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        clip = t["grad_clip"]
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)) if clip \
            else 1.0
        g = {k: x * scale for k, x in g.items()}
        n = (step + 1).astype(jnp.float32)
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            mk = b1 * mom[k] + (1 - b1) * g[k]
            vk = b2 * vel[k] + (1 - b2) * g[k] ** 2
            upd = (mk / (1 - b1 ** n)) / (jnp.sqrt(vk / (1 - b2 ** n)) + eps)
            if decays(k, p[k].shape):
                upd = upd + t["weight_decay"] * pf[k]
            new_p[k] = (pf[k] - lr * upd).astype(p[k].dtype)
            new_m[k], new_v[k] = mk, vk
        norms = {k: jnp.sqrt(jnp.sum(x * x)) for k, x in g.items()}
        return new_p, new_m, new_v, loss, norms

    return jax.jit(step_fn, donate_argnums=(2, 3))


def qat_readings(c: Dict, t: Dict, w: Dict, batches: Sequence[Dict],
                 policy: Dict = QAT_POLICY) -> Dict:
    """Run the reference over ``batches`` from weights ``w``: the loss of
    every step, the per-leaf norms of the first step's clipped gradient,
    and the per-leaf norms of the change over all steps."""
    teacher = dict(w)
    with jax.default_matmul_precision("highest"):
        p0 = {**w, **jax.jit(lambda w: student_scales(w, policy))(w)}
    p = dict(p0)
    mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    vel = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    step_fn = make_train_step(c, t, policy)
    losses: List[float] = []
    grad_norms = None
    p0_host = {k: np.asarray(v.astype(jnp.float32)) for k, v in p0.items()}
    for i, b in enumerate(batches):
        p, mom, vel, loss, norms = step_fn(
            p, teacher, mom, vel, jnp.asarray(b["tokens"][0]),
            jnp.asarray(b["loss_mask"][0], jnp.float32), jnp.int32(i),
            jnp.float32(lr_at(i, t)))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
    change = {k: float(np.linalg.norm(
        np.asarray(v.astype(jnp.float32)).ravel() - p0_host[k].ravel()))
        for k, v in p.items()}
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
