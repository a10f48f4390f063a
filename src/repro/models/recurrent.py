"""Recurrent block family: RG-LRU (RecurrentGemma/Griffin), mLSTM and sLSTM
(xLSTM), and the Mamba-2 mixer (Granite 4.0-H). SiLQ sites: all
projection/gate *linears* carry A-bit input + W4 per-channel weight
quantizers; the element-wise recurrences themselves run
fp32 (they are fp16 ops on NorthPole too — DESIGN.md §Arch-applicability).
The stored recurrent state is the cache analogue and is quantized to C-bits
on the serving path (``state_q`` + scale).

TPU adaptation notes:
* RG-LRU is a diagonal linear recurrence -> ``jax.lax.associative_scan``
  (log-depth, MXU-free but VPU-parallel) instead of a CUDA sequential scan.
* mLSTM's matrix-memory recurrence is linear in the state -> chunked
  parallel form (GLA-style): intra-chunk attention-like einsums feed the
  MXU; inter-chunk state carried by a short scan. Exponential input gating
  is replaced by sigmoid gating for unconditional numerical stability in
  bf16 (documented deviation; the chunked algebra is exact for the gates
  used).
* sLSTM has a non-linearizable hidden->gate feedback -> lax.scan over time
  (small matvecs; it exists in 2/12 layers of the assigned config).
* Mamba-2's SSD is mLSTM's chunked algebra (q = C, k = B, v = x dt,
  log-decay A dt) with B and C shared by all heads; its state is
  (heads, head_dim, d_state).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qat import (QuantCtx, cache_dtype, cache_quantize,
                            init_linear, qlinear, quantize_act)
from repro.core.quantizer import dequantize_int, dynamic_quantize_to_int
from repro.models.common import subcol

MLSTM_CHUNK = 256


# ==========================================================================
# RG-LRU block (Griffin / RecurrentGemma temporal-mixing block)
# ==========================================================================

def init_rglru(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    d, w = cfg.d_model, cfg.resolved_lru_width
    ks = jax.random.split(key, 6)
    # Lambda init so a = exp(-8*softplus(L)*r) spreads over (0.9, 0.999)
    lam = jax.random.uniform(ks[0], (w,), jnp.float32, 0.01, 0.1)
    return {
        "w_in": init_linear(ks[1], d, w, dtype=dtype),
        "w_gate": init_linear(ks[2], d, w, dtype=dtype),
        "conv_w": (jax.random.normal(ks[3], (cfg.conv1d_width, w),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((w,), dtype),
        "w_ig": init_linear(ks[4], w, w, dtype=dtype),   # input gate
        "w_rg": init_linear(ks[5], w, w, dtype=dtype),   # recurrence gate
        "lam": lam,
        "w_out": init_linear(jax.random.fold_in(key, 7), w, d, dtype=dtype),
        "s_state": jnp.float32(1.0),
    }


def _causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                   buf: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Depthwise causal conv, width K. x (B,S,W); buf (B,K-1,W) history."""
    K = w.shape[0]
    if buf is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([buf.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    y = b.astype(jnp.float32)
    for j in range(K):
        y = y + w[j].astype(jnp.float32) * \
            xp[:, j:j + S].astype(jnp.float32)
    return y.astype(x.dtype)


def _rglru_coeffs(cfg, ctx, p, u, col):
    """Gate math shared by train/decode. u: (B,S,W) conv output."""
    if ctx.batch_axes:
        # gate linears are (W, W) with a W-sharded input: without a hint
        # GSPMD all-reduces three fp32 (B,S,W) partial sums per layer; one
        # bf16 all-gather of u is ~8x fewer bytes (EXPERIMENTS.md §Perf D)
        from repro.models.common import shard_hint
        u = shard_hint(u, ctx.batch_axes, None, None)
    i = jax.nn.sigmoid(qlinear(ctx, u, p["w_ig"],
                               subcol(col, "w_ig")).astype(jnp.float32))
    r = jax.nn.sigmoid(qlinear(ctx, u, p["w_rg"],
                               subcol(col, "w_rg")).astype(jnp.float32))
    log_a = -8.0 * jax.nn.softplus(p["lam"]) * r            # (B,S,W)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * i * \
        u.astype(jnp.float32)
    return a, gated


def rglru_fwd(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
              col: Optional[Dict] = None) -> jnp.ndarray:
    """Training/prefill path: associative scan over the diagonal recurrence."""
    gate = jax.nn.gelu(qlinear(ctx, x, p["w_gate"],
                               subcol(col, "w_gate")).astype(jnp.float32))
    u = qlinear(ctx, x, p["w_in"], subcol(col, "w_in"))
    u = _causal_conv1d(u, p["conv_w"], p["conv_b"])
    a, gated = _rglru_coeffs(cfg, ctx, p, u, col)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    h = quantize_act(ctx, h.astype(x.dtype), p, "s_state", col)
    y = (h.astype(jnp.float32) * gate).astype(x.dtype)
    return qlinear(ctx, y, p["w_out"], subcol(col, "w_out"))


def init_rglru_cache(cfg: ModelConfig, B: int, dtype=jnp.int8) -> Dict:
    w = cfg.resolved_lru_width
    return {"state_q": jnp.zeros((B, w), dtype),
            "s_state": jnp.zeros((B, 1), jnp.float32),
            "conv_buf": jnp.zeros((B, cfg.conv1d_width - 1, w),
                                  jnp.bfloat16)}


def rglru_prefill(cfg, ctx, p, x, col=None):
    """Prefill: run the parallel scan, emit final quantized state."""
    gate = jax.nn.gelu(qlinear(ctx, x, p["w_gate"],
                               subcol(col, "w_gate")).astype(jnp.float32))
    u = qlinear(ctx, x, p["w_in"], subcol(col, "w_in"))
    uc = _causal_conv1d(u, p["conv_w"], p["conv_b"])
    a, gated = _rglru_coeffs(cfg, ctx, p, uc, col)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    hq = quantize_act(ctx, h.astype(x.dtype), p, "s_state", col)
    y = (hq.astype(jnp.float32) * gate).astype(x.dtype)
    y = qlinear(ctx, y, p["w_out"], subcol(col, "w_out"))
    state_q, s_state = cache_quantize(ctx, h[:, -1].astype(jnp.bfloat16))
    K = cfg.conv1d_width
    cache = {"state_q": state_q, "s_state": s_state,
             "conv_buf": u[:, -(K - 1):].astype(jnp.bfloat16)}
    return y, cache


def rglru_decode(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x1: jnp.ndarray,
                 cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    gate = jax.nn.gelu(qlinear(ctx, x1, p["w_gate"]).astype(jnp.float32))
    u = qlinear(ctx, x1, p["w_in"])                       # (B,1,W)
    uc = _causal_conv1d(u, p["conv_w"], p["conv_b"], buf=cache["conv_buf"])
    a, gated = _rglru_coeffs(cfg, ctx, p, uc, None)       # (B,1,W)
    h_prev = dequantize_int(cache["state_q"], cache["s_state"],
                            jnp.float32)                  # (B,W)
    h = a[:, 0] * h_prev + gated[:, 0]
    state_q, s_state = cache_quantize(ctx, h.astype(jnp.bfloat16))
    y = (h[:, None] * gate).astype(x1.dtype)
    y = qlinear(ctx, y, p["w_out"])
    new_buf = jnp.concatenate([cache["conv_buf"][:, 1:],
                               u.astype(jnp.bfloat16)], axis=1)
    return y, {"state_q": state_q, "s_state": s_state, "conv_buf": new_buf}


# ==========================================================================
# Chunked linear recurrences (mLSTM, Mamba-2 SSD)
# ==========================================================================

def _causal_decay(cum: jnp.ndarray) -> jnp.ndarray:
    """exp(cum_t - cum_s) for s <= t, else 0, from the inclusive cumsum
    ``cum`` (..., L) of a chunk's log-decays: (..., L, L) over (t, s)."""
    L = cum.shape[-1]
    decay = cum[..., :, None] - cum[..., None, :]
    tri = jnp.tril(jnp.ones((L, L), bool))
    # mask BEFORE exp: upper-triangle decay is positive and can overflow,
    # poisoning the where() gradient with inf * 0 = NaN
    return jnp.exp(jnp.where(tri, decay, -jnp.inf))


# ==========================================================================
# mLSTM block (xLSTM matrix memory, chunked parallel form)
# ==========================================================================

def init_mlstm(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    d = cfg.d_model
    m = int(cfg.mlstm_proj_factor * d)
    ks = jax.random.split(key, 6)
    return {
        "w_up": init_linear(ks[0], d, 2 * m, dtype=dtype),
        "w_q": init_linear(ks[1], m, m, dtype=dtype),
        "w_k": init_linear(ks[2], m, m, dtype=dtype),
        "w_v": init_linear(ks[3], m, m, dtype=dtype),
        "w_gates": init_linear(ks[4], m, 2 * cfg.n_heads, bias=True,
                               dtype=dtype),
        "w_down": init_linear(ks[5], m, d, dtype=dtype),
        "s_q": jnp.float32(1.0), "s_k": jnp.float32(1.0),
        "s_v": jnp.float32(1.0), "s_state": jnp.float32(1.0),
    }


def _mlstm_qkv(cfg, ctx, p, x, col):
    m = int(cfg.mlstm_proj_factor * cfg.d_model)
    H = cfg.n_heads
    dh = m // H
    B, S, _ = x.shape
    up = qlinear(ctx, x, p["w_up"], subcol(col, "w_up"))
    u, z = up[..., :m], up[..., m:]
    q = qlinear(ctx, u, p["w_q"], subcol(col, "w_q")).reshape(B, S, H, dh)
    k = qlinear(ctx, u, p["w_k"], subcol(col, "w_k")).reshape(B, S, H, dh)
    v = qlinear(ctx, u, p["w_v"], subcol(col, "w_v")).reshape(B, S, H, dh)
    q = quantize_act(ctx, q, p, "s_q", col)
    k = quantize_act(ctx, k, p, "s_k", col)
    v = quantize_act(ctx, v, p, "s_v", col)
    gates = qlinear(ctx, u, p["w_gates"],
                    subcol(col, "w_gates")).astype(jnp.float32)
    ig = jax.nn.sigmoid(gates[..., :H])                  # (B,S,H)
    lf = jax.nn.log_sigmoid(gates[..., H:])              # log forget gate
    return q, k, v, z, ig, lf, dh


def mlstm_fwd(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
              col: Optional[Dict] = None, *, return_state: bool = False):
    """Chunked linear recurrence: C_t = f_t C_{t-1} + i_t k_t v_t^T,
    h_t = (q_t C_t) / max(|q_t n_t|, 1) with the normalizer n carried as an
    extra value column."""
    B, S, d = x.shape
    q, k, v, z, ig, lf, dh = _mlstm_qkv(cfg, ctx, p, x, col)
    H = cfg.n_heads
    L = min(MLSTM_CHUNK, S)
    nc = -(-S // L)
    pad = nc * L - S

    def pad_t(t, val=0.0):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2),
                       constant_values=val) if pad else t

    qc = pad_t(q).reshape(B, nc, L, H, dh)
    kc = pad_t(k).reshape(B, nc, L, H, dh)
    vc = pad_t(v).reshape(B, nc, L, H, dh)
    igc = pad_t(ig).reshape(B, nc, L, H)
    lfc = pad_t(lf).reshape(B, nc, L, H)   # pad log-f with 0 (f=1, harmless)
    scale = dh ** -0.5

    def vi_n(vi):
        """Append the normalizer ones-column to a value chunk."""
        return jnp.concatenate(
            [vi.astype(jnp.float32),
             jnp.ones_like(vi[..., :1], jnp.float32)], axis=-1)

    def chunk(state, inp):
        qi, ki, vi, ii, lfi = inp            # (B,L,H,*) for this chunk
        cum = jnp.cumsum(lfi, axis=1)        # inclusive cumsum of log f
        # intra-chunk: decay(t, tau) = exp(cum_t - cum_tau) for tau <= t
        qf = qi.astype(jnp.float32) * scale
        kf = ki.astype(jnp.float32)
        scores = jnp.einsum("bthd,bshd->bhts", qf, kf)
        dmask = _causal_decay(jnp.moveaxis(cum, 1, 2))    # (B,H,t,s)
        w_ts = scores * dmask * \
            jnp.moveaxis(ii, 2, 1)[:, :, None, :].astype(jnp.float32)
        intra = jnp.einsum("bhts,bshe->bthe", w_ts, vi_n(vi))
        # inter-chunk: q_t exp(cum_t) @ state
        qdec = qf * jnp.exp(cum)[..., None]
        inter = jnp.einsum("bthd,bhde->bthe", qdec, state)
        out = intra + inter                                # (B,L,H,dh+1)
        # state update
        tot = cum[:, -1]                                   # (B,H)
        kdec = kf * (jnp.exp(tot[:, None] - cum) *
                     ii.astype(jnp.float32))[..., None]
        kv = jnp.einsum("bshd,bshe->bhde", kdec, vi_n(vi))
        state = state * jnp.exp(tot)[..., None, None] + kv
        return state, out

    state0 = jnp.zeros((B, H, dh, dh + 1), jnp.float32)
    state, outs = jax.lax.scan(
        chunk, state0,
        (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(kc, 1, 0),
         jnp.moveaxis(vc, 1, 0), jnp.moveaxis(igc, 1, 0),
         jnp.moveaxis(lfc, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nc * L, H, dh + 1)[:, :S]
    num, den = out[..., :dh], out[..., dh]
    h = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    m = int(cfg.mlstm_proj_factor * d)
    h = h.reshape(B, S, m).astype(x.dtype)
    h = quantize_act(ctx, h, p, "s_state", col)
    y = h * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    y = qlinear(ctx, y, p["w_down"], subcol(col, "w_down"))
    if return_state:
        return y, state
    return y


def init_mlstm_cache(cfg: ModelConfig, B: int, dtype=jnp.int8) -> Dict:
    m = int(cfg.mlstm_proj_factor * cfg.d_model)
    H = cfg.n_heads
    dh = m // H
    return {"state_q": jnp.zeros((B, H, dh, dh + 1), dtype),
            "s_state": jnp.zeros((B, H, 1, 1), jnp.float32)}


def mlstm_prefill(cfg, ctx, p, x, col=None):
    y, state = mlstm_fwd(cfg, ctx, p, x, col, return_state=True)
    B, H = state.shape[:2]
    sq, ss = cache_quantize(ctx, state.reshape(B, H, -1).astype(jnp.bfloat16))
    return y, {"state_q": sq.reshape(state.shape),
               "s_state": ss[..., None]}


def mlstm_decode(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x1: jnp.ndarray,
                 cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    B = x1.shape[0]
    q, k, v, z, ig, lf, dh = _mlstm_qkv(cfg, ctx, p, x1, None)
    H = cfg.n_heads
    state = dequantize_int(cache["state_q"], cache["s_state"], jnp.float32)
    f = jnp.exp(lf[:, 0]).astype(jnp.float32)             # (B,H)
    i = ig[:, 0].astype(jnp.float32)
    vn = jnp.concatenate([v[:, 0].astype(jnp.float32),
                          jnp.ones((B, H, 1), jnp.float32)], axis=-1)
    kv = jnp.einsum("bhd,bhe->bhde", k[:, 0].astype(jnp.float32) *
                    i[..., None], vn)
    state = state * f[..., None, None] + kv
    qf = q[:, 0].astype(jnp.float32) * dh ** -0.5
    out = jnp.einsum("bhd,bhde->bhe", qf, state)
    num, den = out[..., :dh], out[..., dh]
    h = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    m = int(cfg.mlstm_proj_factor * cfg.d_model)
    h = h.reshape(B, 1, m).astype(x1.dtype)
    h = quantize_act(ctx, h, p, "s_state")
    y = h * jax.nn.silu(z.astype(jnp.float32)).astype(x1.dtype)
    y = qlinear(ctx, y, p["w_down"])
    sq, ss = cache_quantize(ctx, state.reshape(B, H, -1).astype(jnp.bfloat16))
    return y, {"state_q": sq.reshape(state.shape), "s_state": ss[..., None]}


# ==========================================================================
# sLSTM block (scalar memory, sequential scan)
# ==========================================================================

def init_slstm(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    d = cfg.d_model
    s_in = int(cfg.slstm_proj_factor * d)
    ks = jax.random.split(key, 4)
    return {
        "w_x": init_linear(ks[0], d, 4 * d, bias=True, dtype=dtype),
        "r_h": init_linear(ks[1], d, 4 * d, dtype=dtype),
        "w_up": init_linear(ks[2], d, s_in, dtype=dtype),
        "w_down": init_linear(ks[3], s_in, d, dtype=dtype),
        "s_state": jnp.float32(1.0),
    }


def _slstm_cell(cfg, ctx, p, gx_t, h_prev, c_prev):
    """One sLSTM step. gx_t: precomputed W_x x_t (B,4d)."""
    d = cfg.d_model
    rh = qlinear(ctx, h_prev, p["r_h"])
    g = (gx_t + rh).astype(jnp.float32)
    i, f, zz, o = jnp.split(g, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(zz)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h.astype(gx_t.dtype), c


def slstm_fwd(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
              col: Optional[Dict] = None, *, return_state: bool = False):
    B, S, d = x.shape
    gx = qlinear(ctx, x, p["w_x"], subcol(col, "w_x"))     # (B,S,4d)

    def step(carry, gx_t):
        h, c = carry
        h, c = _slstm_cell(cfg, ctx, p, gx_t, h, c)
        return (h, c), h

    h0 = jnp.zeros((B, d), gx.dtype)
    c0 = jnp.zeros((B, d), jnp.float32)
    (hT, cT), hs = jax.lax.scan(step, (h0, c0), jnp.moveaxis(gx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1)                             # (B,S,d)
    h = quantize_act(ctx, h, p, "s_state", col)
    u = qlinear(ctx, h, p["w_up"], subcol(col, "w_up"))
    u = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    y = qlinear(ctx, u, p["w_down"], subcol(col, "w_down"))
    if return_state:
        return y, (hT, cT)
    return y


def init_slstm_cache(cfg: ModelConfig, B: int, dtype=jnp.int8) -> Dict:
    d = cfg.d_model
    return {"state_q": jnp.zeros((B, d), dtype),
            "s_state": jnp.zeros((B, 1), jnp.float32),
            "c": jnp.zeros((B, d), jnp.float32)}


def slstm_prefill(cfg, ctx, p, x, col=None):
    y, (hT, cT) = slstm_fwd(cfg, ctx, p, x, col, return_state=True)
    hq, hs = cache_quantize(ctx, hT.astype(jnp.bfloat16))
    return y, {"state_q": hq, "s_state": hs, "c": cT.astype(jnp.float32)}


def slstm_decode(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x1: jnp.ndarray,
                 cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    gx = qlinear(ctx, x1, p["w_x"])[:, 0]
    h_prev = dequantize_int(cache["state_q"], cache["s_state"],
                            x1.dtype)
    h, c = _slstm_cell(cfg, ctx, p, gx, h_prev, cache["c"])
    hq2 = quantize_act(ctx, h[:, None], p, "s_state")
    u = qlinear(ctx, hq2, p["w_up"])
    u = jax.nn.gelu(u.astype(jnp.float32)).astype(x1.dtype)
    y = qlinear(ctx, u, p["w_down"])
    hq, hs = cache_quantize(ctx, h.astype(jnp.bfloat16))
    return y, {"state_q": hq, "s_state": hs, "c": c.astype(jnp.float32)}


# ==========================================================================
# Mamba-2 mixer (SSD, chunked parallel form)
# ==========================================================================

def init_mamba2(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    """in_proj d -> z | x,B,C | dt; depthwise conv over x,B,C; per-head
    A_log, D, dt_bias (f32); gated RMSNorm gain; out_proj inner -> d.
    A = -exp(A_log) with A in [1, 16]; dt = softplus(dt_bias) log-uniform
    in [1e-3, 0.1] (the Mamba-2 initialisation)."""
    d, inner, N, H = cfg.d_model, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = inner + 2 * N
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(0.1)))
    return {
        "in_proj": init_linear(ks[0], d, inner + conv_ch + H, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv1d_width, conv_ch),
                                     jnp.float32)
                   * cfg.conv1d_width ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jax.random.uniform(ks[2], (H,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "norm": {"w": jnp.ones((inner,), dtype)},
        "out_proj": init_linear(ks[4], inner, d, dtype=dtype),
    }


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int,
                state0: Optional[jnp.ndarray] = None):
    """The SSD recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
    y_t = h_t C_t, in f32, chunk by chunk: mLSTM's algebra with q = C,
    k = B, v = x dt and log-decay A dt, B and C shared by the heads.

    x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N); ``state0``
    (B,H,P,N) or zeros. Returns (y (B,S,H,P), final state (B,H,P,N))."""
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    nc = -(-S // L)
    pad = nc * L - S
    f32 = jnp.float32
    a = dt.astype(f32) * A.astype(f32)                  # log-decay (B,S,H)
    v = x.astype(f32) * dt.astype(f32)[..., None]

    def chunks(t):
        # padded steps decay by exp(0) and add nothing: the state is exact
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) \
            if pad else t
        return jnp.moveaxis(t.reshape((Bb, nc, L) + t.shape[2:]), 1, 0)

    def chunk_fn(state, inp):
        ai, vi, bi, ci = inp                 # (B,L,H) (B,L,H,P) (B,L,N) x2
        cum = jnp.cumsum(jnp.moveaxis(ai, 1, 2), axis=-1)      # (B,H,L)
        cb = jnp.einsum("btn,bsn->bts", ci, bi)
        w = _causal_decay(cum) * cb[:, None]                    # (B,H,t,s)
        intra = jnp.einsum("bhts,bshp->bthp", w, vi)
        inter = jnp.einsum("btn,bhpn->bthp", ci, state) * \
            jnp.moveaxis(jnp.exp(cum), 1, 2)[..., None]
        tot = cum[..., -1]                                      # (B,H)
        kdec = jnp.exp(tot[..., None] - cum)                    # (B,H,L)
        state = state * jnp.exp(tot)[..., None, None] + jnp.einsum(
            "bshp,bhs,bsn->bhpn", vi, kdec, bi)
        return state, intra + inter

    if state0 is None:
        state0 = jnp.zeros((Bb, H, P, N), f32)
    state, ys = jax.lax.scan(
        chunk_fn, state0.astype(f32),
        (chunks(a), chunks(v), chunks(Bm.astype(f32)),
         chunks(Cm.astype(f32))))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bb, nc * L, H, P)[:, :S]
    return y, state


def _mamba2_in(cfg, ctx, p, x, col):
    """in_proj, split into z, the conv's input x|B|C, and dt."""
    inner, N = cfg.ssm_inner, cfg.ssm_state
    zxbcdt = qlinear(ctx, x, p["in_proj"], subcol(col, "in_proj"))
    return (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * N],
            zxbcdt[..., 2 * inner + 2 * N:])


def _mamba2_streams(cfg, p, xbc, dt, buf=None):
    """Conv + SiLU over x|B|C, then x (B,S,H,P), B, C and dt, in f32."""
    inner, N = cfg.ssm_inner, cfg.ssm_state
    xbc = jax.nn.silu(_causal_conv1d(xbc.astype(jnp.float32), p["conv_w"],
                                     p["conv_b"], buf=buf))
    B_, S = xbc.shape[:2]
    xs = xbc[..., :inner].reshape(B_, S, cfg.ssm_heads, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    return xs, xbc[..., inner:inner + N], xbc[..., inner + N:], dt


def _mamba2_out(cfg, ctx, p, y, z, x_dtype, col):
    """Gated RMSNorm of y * silu(z) over the inner width, then out_proj."""
    B_, S = y.shape[:2]
    g = y.reshape(B_, S, cfg.ssm_inner) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
    g = (g * p["norm"]["w"].astype(jnp.float32)).astype(x_dtype)
    return qlinear(ctx, g, p["out_proj"], subcol(col, "out_proj"))


def mamba2_fwd(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
               col: Optional[Dict] = None, *, return_cache: bool = False):
    """Training/prefill path. Only in_proj and out_proj carry quantizers
    (A-bit input, W-bit weights); the conv, dt, the SSD, the D skip and the
    gated norm run in f32. With ``return_cache`` also the dense serving
    cache: the SSD state (C-bit) and the conv's last K-1 inputs."""
    z, xbc, dt = _mamba2_in(cfg, ctx, p, x, col)
    xs, Bm, Cm, dt = _mamba2_streams(cfg, p, xbc, dt)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("ssd"):
        y, state = ssd_chunked(xs, dt, A, Bm, Cm, cfg.ssm_chunk)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    out = _mamba2_out(cfg, ctx, p, y, z, x.dtype, col)
    if not return_cache:
        return out
    K = cfg.conv1d_width
    tail = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))[:, -(K - 1):]
    return out, _mamba2_cache(ctx, state, tail)


def _mamba2_cache(ctx, state, conv_buf):
    sq, ss = cache_quantize(ctx, state.astype(jnp.bfloat16))
    return {"state_q": sq, "s_state": ss,
            "conv_buf": conv_buf.astype(jnp.bfloat16)}


def init_mamba2_cache(cfg: ModelConfig, B: int, dtype=jnp.int8) -> Dict:
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return {"state_q": jnp.zeros((B, H, P, N), dtype),
            "s_state": jnp.zeros((B, H, P, 1), jnp.float32),
            "conv_buf": jnp.zeros((B, cfg.conv1d_width - 1,
                                   cfg.ssm_inner + 2 * cfg.ssm_state),
                                  jnp.bfloat16)}


def mamba2_decode(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x1: jnp.ndarray,
                  cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One token: the conv over the cached inputs, one SSD step on the
    dequantized state."""
    z, xbc, dt = _mamba2_in(cfg, ctx, p, x1, None)
    xs, Bm, Cm, dt = _mamba2_streams(cfg, p, xbc, dt, buf=cache["conv_buf"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    h = dequantize_int(cache["state_q"], cache["s_state"], jnp.float32)
    x0, dt0, B0, C0 = xs[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0]
    h = h * jnp.exp(dt0 * A)[..., None, None] + jnp.einsum(
        "bhp,bn->bhpn", x0 * dt0[..., None], B0)
    y = jnp.einsum("bhpn,bn->bhp", h, C0) + \
        p["D"].astype(jnp.float32)[:, None] * x0
    out = _mamba2_out(cfg, ctx, p, y[:, None], z, x1.dtype, None)
    buf = jnp.concatenate([cache["conv_buf"][:, 1:],
                           xbc.astype(jnp.bfloat16)], axis=1)
    return out, _mamba2_cache(ctx, h, buf)
