"""Transformer block family: GQA attention (global / sliding-window / cross),
dense SwiGLU / GELU MLP, and GShard-style top-k MoE — all with SiLQ
quantization sites attached per paper Fig. 2:

* every linear: input A-bits (``s_in``), weight W-bits per-out-channel (``s_w``)
* query into QK^T: 16-bit (``s_q``)
* K/V written to cache: C-bits (``s_k``/``s_v``)
* softmax output: unquantized during training (flash-attention policy)
* MoE router: 8-bit weight/act (accuracy-critical, tiny)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qat import (QuantCtx, init_linear, qlinear, quantize_act,
                            quantize_weight_p)
from repro.core.quantizer import dynamic_quantize_to_int, quantize_to_int
from repro.models.common import (apply_rope, blockwise_attention,
                                 decode_attention_intcache, head_rms_norm,
                                 init_norm, norm, subcol)

MOE_CAPACITY_FACTOR = 1.25
MOE_CHUNK_S = 1024      # sequence-chunk for the dispatch working set


def _decode_attn(q, k_q, v_q, s_k, s_v, lengths, mesh=None) -> jnp.ndarray:
    """Decode attention over the int cache for a full slot batch.

    On TPU this is the Pallas flash-decode kernel (int8 tiles dequantized
    VMEM-locally, one grid row per slot); elsewhere the fused XLA path.
    Both take the same batched (B, ...) operands, so the serve engine's
    whole-slot decode step is backend-independent.
    """
    if jax.default_backend() == "tpu":
        from repro.kernels.kvq_attn.ops import kvq_decode_attn
        return kvq_decode_attn(q, k_q, v_q, s_k, s_v, lengths, mesh=mesh)
    return decode_attention_intcache(q, k_q, v_q, s_k, s_v, lengths)


def _decode_attn_paged(q, k_pool, v_pool, s_k, s_v, block_tbl,
                       lengths, mesh=None) -> jnp.ndarray:
    """Decode attention through a block table over the global cache pool.

    On TPU the Pallas paged kernel walks the slot's blocks directly (the
    table is a scalar-prefetch operand of the grid); elsewhere we gather the
    slot's blocks into a contiguous view and reuse the same fused XLA path
    as the dense cache, so dense and paged decode agree bitwise on CPU.
    """
    if jax.default_backend() == "tpu":
        from repro.kernels.kvq_attn.ops import kvq_paged_decode_attn
        return kvq_paged_decode_attn(q, k_pool, v_pool, s_k, s_v,
                                     block_tbl, lengths, mesh=mesh)
    from repro.kernels.kvq_attn.ref import gather_paged_kv
    return decode_attention_intcache(
        q, gather_paged_kv(k_pool, block_tbl),
        gather_paged_kv(v_pool, block_tbl),
        gather_paged_kv(s_k, block_tbl),
        gather_paged_kv(s_v, block_tbl), lengths)


def _spec_verify_attn(q, k_pool, v_pool, s_k, s_v, block_tbl,
                      lengths, mesh=None) -> jnp.ndarray:
    """Multi-query decode attention for the speculative verify-wave.

    q (n, C, H, D): C window queries per slot whose quantized K/V are
    already committed to the pool; lengths (n, C): query j reads cache
    positions ``< lengths[n, j]``. On TPU one widened Pallas kernel
    serves all C queries per block-table walk; elsewhere the gather +
    per-position decode oracle runs — each position computes exactly the
    ops a sequential ``decode_step`` would, so the verified stream is
    bitwise identical to plain decode.
    """
    from repro.kernels.kvq_attn.ops import kvq_spec_verify_attn
    return kvq_spec_verify_attn(q, k_pool, v_pool, s_k, s_v, block_tbl,
                                lengths,
                                use_pallas=jax.default_backend() == "tpu",
                                mesh=mesh)


# ==========================================================================
# Dense MLPs
# ==========================================================================

def init_mlp(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {"wg": init_linear(ks[0], d, f, dtype=dtype),
                "wu": init_linear(ks[1], d, f, dtype=dtype),
                "wd": init_linear(ks[2], f, d, dtype=dtype)}
    return {"w1": init_linear(ks[0], d, f, bias=True, dtype=dtype),
            "w2": init_linear(ks[1], f, d, bias=True, dtype=dtype)}


def mlp_fwd(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
            col: Optional[Dict] = None) -> jnp.ndarray:
    if cfg.mlp_type == "swiglu":
        g = qlinear(ctx, x, p["wg"], subcol(col, "wg"))
        u = qlinear(ctx, x, p["wu"], subcol(col, "wu"))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return qlinear(ctx, h, p["wd"], subcol(col, "wd"))
    h = qlinear(ctx, x, p["w1"], subcol(col, "w1"))
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return qlinear(ctx, h, p["w2"], subcol(col, "w2"))


# ==========================================================================
# Mixture of Experts (GShard capacity dispatch, chunked over tokens)
# ==========================================================================

def init_moe(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)

    def expert_w(k, din, dout):
        w = (jax.random.normal(k, (e, din, dout), jnp.float32)
             * din ** -0.5).astype(dtype)
        return {"w": w, "s_w": jnp.ones((e, 1, dout), jnp.float32),
                "s_in": jnp.float32(1.0)}

    return {"router": init_linear(ks[0], d, e, dtype=dtype),
            "wg": expert_w(ks[1], d, f),
            "wu": expert_w(ks[2], d, f),
            "wd": expert_w(ks[3], f, d)}


def _expert_linear(ctx: QuantCtx, x: jnp.ndarray, p: Dict,
                   col: Optional[Dict]) -> jnp.ndarray:
    """x: (B, E, C, din) -> (B, E, C, dout), quantized acts + expert weights."""
    xq = quantize_act(ctx, x, p, "s_in", col)
    wq = quantize_weight_p(ctx, p)
    return jnp.einsum("becd,edf->becf", xq, wq)


def moe_fwd(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
            col: Optional[Dict] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Token-choice top-k MoE with *per-batch-row* capacity dispatch.

    Sharding-aware by construction: routing, position-in-expert, and the
    dispatch/combine one-hots are computed independently per batch row, so
    the batch axis stays data-sharded end to end (no sharded-dim scan, no
    cross-device cumsum) and the experts axis shards over "model" (EP) or
    d_ff does (TP). Chunked over sequence to bound the one-hot working set.
    Returns (y, load-balance aux).
    """
    e, k = cfg.n_experts, cfg.n_experts_active
    B, S, d = x.shape
    sc = min(MOE_CHUNK_S, S)
    nchunk = -(-S // sc)
    pad = nchunk * sc - S
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    cap = max(1, int(round(sc * k / e * MOE_CAPACITY_FACTOR)))
    cap = min(cap + (-cap) % 4 if cap >= 4 else cap, sc * k)

    def chunk(carry, xc):                               # xc: (B, sc, d)
        logits = qlinear(ctx, xc, p["router"], subcol(col, "router"),
                         act_bits=8, weight_bits=8).astype(jnp.float32)
        vals, idx = jax.lax.top_k(logits, k)            # (B, sc, k)
        gates = jax.nn.softmax(vals, axis=-1)
        oh = jax.nn.one_hot(idx, e, dtype=jnp.bfloat16)  # (B, sc, k, e)
        # position of each (token, slot) within its expert, counted along
        # the flattened (s, k) order *within this row*
        flat = oh.astype(jnp.float32).reshape(B, sc * k, e)
        pos = (jnp.cumsum(flat, axis=1) - flat).reshape(B, sc, k, e)
        pos = jnp.sum(pos * oh.astype(jnp.float32), axis=-1)  # (B, sc, k)
        keep = pos < cap
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.bfloat16) \
            * keep[..., None]                           # (B, sc, k, cap)
        dispatch = jnp.einsum("bske,bskc->bsec", oh, pos_oh,
                              preferred_element_type=jnp.bfloat16)
        combine = jnp.einsum("bske,bskc,bsk->bsec", oh, pos_oh,
                             gates.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
        xe = jnp.einsum("bsec,bsd->becd", dispatch, xc.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        xe = xe.astype(x.dtype)                         # (B, e, cap, d)
        g = _expert_linear(ctx, xe, p["wg"], subcol(col, "wg"))
        u = _expert_linear(ctx, xe, p["wu"], subcol(col, "wu"))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        ye = _expert_linear(ctx, h, p["wd"], subcol(col, "wd"))
        yc = jnp.einsum("bsec,becd->bsd", combine.astype(jnp.bfloat16),
                ye.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)
        # load-balance aux (Switch): e * sum_e(frac_tokens_e * frac_prob_e)
        probs = jax.nn.softmax(logits, axis=-1)
        frac_tok = jnp.mean(jnp.sum(oh, axis=2), axis=(0, 1))
        frac_prob = jnp.mean(probs, axis=(0, 1))
        aux = e * jnp.sum(frac_tok * frac_prob)
        return carry, (yc.astype(x.dtype), aux)

    if nchunk == 1:
        _, (y, aux) = chunk(None, xs)
        y, auxs = y, aux[None]
    else:
        _, (ys, auxs) = jax.lax.scan(
            chunk, None,
            jnp.moveaxis(xs.reshape(B, nchunk, sc, d), 1, 0))
        y = jnp.moveaxis(ys, 0, 1).reshape(B, nchunk * sc, d)
    return y[:, :S], jnp.mean(auxs)


# ==========================================================================
# Attention block (self / cross), with quantized KV cache
# ==========================================================================

def init_attention(cfg: ModelConfig, key, cross: bool = False,
                   dtype=jnp.bfloat16) -> Dict:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p = {"wq": init_linear(ks[0], d, qd, bias=cfg.qkv_bias, dtype=dtype),
         "wk": init_linear(ks[1], d, kvd, bias=cfg.qkv_bias, dtype=dtype),
         "wv": init_linear(ks[2], d, kvd, bias=cfg.qkv_bias, dtype=dtype),
         "wo": init_linear(ks[3], qd, d, dtype=dtype),
         "s_q": jnp.float32(1.0), "s_k": jnp.float32(1.0),
         "s_v": jnp.float32(1.0)}
    if cfg.qk_norm and not cross:
        hd = cfg.resolved_head_dim
        p["q_norm"] = {"w": jnp.ones((hd,), dtype)}
        p["k_norm"] = {"w": jnp.ones((hd,), dtype)}
    return p


def _qkv(cfg: ModelConfig, ctx: QuantCtx, p: Dict, xq: jnp.ndarray,
         xkv: jnp.ndarray, rope, col, *, skip_rope: bool = False):
    hd = cfg.resolved_head_dim
    B, Sq = xq.shape[0], xq.shape[1]
    Skv = xkv.shape[1]
    q = qlinear(ctx, xq, p["wq"], subcol(col, "wq")).reshape(
        B, Sq, cfg.n_heads, hd)
    k = qlinear(ctx, xkv, p["wk"], subcol(col, "wk")).reshape(
        B, Skv, cfg.n_kv_heads, hd)
    v = qlinear(ctx, xkv, p["wv"], subcol(col, "wv")).reshape(
        B, Skv, cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q = head_rms_norm(q, p["q_norm"]["w"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"]["w"], cfg.norm_eps)
    if rope is not None and not skip_rope:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if cfg.attention_multiplier:
        # the attention functions scale scores by 1/sqrt(hd): fold the
        # config's score scale (Granite's 1/hd) into q
        q = q * (cfg.attention_multiplier * hd ** 0.5)
    # paper sites: query INT16, cache C-bits
    q = quantize_act(ctx, q, p, "s_q", col)
    k = quantize_act(ctx, k, p, "s_k", col)
    v = quantize_act(ctx, v, p, "s_v", col)
    # distribution hints: when GQA kv-heads don't divide the TP axis, GSPMD
    # otherwise splits head_dim and all-reduces every score tile (the
    # dominant collective). Replicate K/V over "model" and shard either the
    # q heads ("kv_rep") or the q sequence ("seq") instead.
    if ctx.attn_shard_mode:
        from repro.models.common import shard_hint
        dp = ctx.batch_axes or None
        if ctx.attn_shard_mode == "tp":
            # serve-side tensor parallelism: q AND kv heads shard over
            # "model" (the engine only selects this mode when both head
            # counts divide), so attention is head-local per device and
            # the block-pool commit stays collective-free
            q = shard_hint(q, dp, None, "model", None)
            k = shard_hint(k, dp, None, "model", None)
            v = shard_hint(v, dp, None, "model", None)
            return q, k, v
        if ctx.attn_shard_mode == "kv_rep":
            q = shard_hint(q, dp, None, "model", None)
        elif ctx.attn_shard_mode == "seq":
            q = shard_hint(q, dp, "model", None, None)
        k = shard_hint(k, dp, None, None, None)
        v = shard_hint(v, dp, None, None, None)
    return q, k, v


def attn_fwd(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
             rope, col: Optional[Dict] = None, *, window: int = 0,
             enc_out: Optional[jnp.ndarray] = None,
             causal: bool = True) -> jnp.ndarray:
    """Self- (enc_out=None) or cross-attention, training/prefill path."""
    B, S, _ = x.shape
    xkv = enc_out if enc_out is not None else x
    q, k, v = _qkv(cfg, ctx, p, x, xkv, rope, col,
                   skip_rope=enc_out is not None)
    # sequence-parallel attention keeps q positions sharded: one q block
    # (chunking the sharded S would put a scan on a sharded axis)
    qc = S if ctx.attn_shard_mode == "seq" else 1024
    out = blockwise_attention(q, k, v,
                              causal=causal and enc_out is None,
                              window=window, q_chunk=qc,
                              kv_chunk=512 if qc == S else 1024)
    out = out.reshape(B, S, cfg.q_dim)
    return qlinear(ctx, out, p["wo"], subcol(col, "wo"))


def quantize_kv_for_cache(ctx: QuantCtx, p: Dict, k: jnp.ndarray,
                          v: jnp.ndarray):
    """(B,S,Hkv,D) bf16 -> cache layout (B,Hkv,S,D) + (B,Hkv,S) scales.

    Dynamic policy: per-token absmax int scales. Static policy: the learned
    LSQ scale broadcast per token. C16/off: bf16 storage, unit scales
    (uniform cache format across policies).
    """
    from repro.core.qat import cache_quantize
    bits = ctx.policy.cache_bits
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if ctx.off or bits >= 16 or ctx.policy.act_dynamic:
        k_q, s_k = cache_quantize(ctx, kt, axis=-1)
        v_q, s_v = cache_quantize(ctx, vt, axis=-1)
        return k_q, v_q, s_k[..., 0], s_v[..., 0]
    s_k = jnp.broadcast_to(p["s_k"], kt.shape[:-1]).astype(jnp.float32)
    s_v = jnp.broadcast_to(p["s_v"], vt.shape[:-1]).astype(jnp.float32)
    return (quantize_to_int(kt, s_k[..., None], bits),
            quantize_to_int(vt, s_v[..., None], bits), s_k, s_v)


def attn_prefill(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x: jnp.ndarray,
                 rope, col=None, *, window: int = 0, cache_len: int = 0,
                 enc_out: Optional[jnp.ndarray] = None,
                 lengths: Optional[jnp.ndarray] = None,
                 page_size: int = 0):
    """Like attn_fwd but also emits the quantized cache for serving.

    ``lengths`` (B,) marks the valid (right-padded) prefix of each row:
    pad-position K/V are dropped from the cache and ``cache["length"]``
    tracks the true per-row length, so a single padded prefill call can
    admit prompts of different lengths (causality keeps real-token outputs
    independent of the padding).

    ``page_size`` > 0 switches the emitted cache to *block shape*
    (B, nb, Hkv, page_size, D): the engine scatters those blocks into the
    global pool through the slot's block table instead of copying a dense
    stripe. Attention math is identical either way; only the commit layout
    changes. Requires window == 0 (paged layers are full attention).
    """
    B, S, _ = x.shape
    xkv = enc_out if enc_out is not None else x
    q, k, v = _qkv(cfg, ctx, p, x, xkv, rope, col,
                   skip_rope=enc_out is not None)
    qc = S if ctx.attn_shard_mode == "seq" else 1024
    out = blockwise_attention(q, k, v, causal=enc_out is None, window=window,
                              q_chunk=qc, kv_chunk=512 if qc == S else 1024)
    out = out.reshape(B, S, cfg.q_dim)
    y = qlinear(ctx, out, p["wo"], subcol(col, "wo"))
    k_q, v_q, s_k, s_v = quantize_kv_for_cache(ctx, p, k, v)
    S_in = k.shape[1]
    if page_size:
        if window:
            raise ValueError("paged cache layout requires full attention "
                             "(window == 0)")
        if lengths is None:
            lengths = jnp.full((B,), S_in, jnp.int32)
        cache = _paginate_kv(k_q, v_q, s_k, s_v, page_size)
        cache["length"] = lengths.astype(jnp.int32)
        return y, cache
    Sc = cache_len or S_in
    if window:
        Sc = min(Sc, window)   # ring eviction enforces the sliding window
    cache = _blank_attn_cache(B, cfg, Sc, k_q.dtype)
    if lengths is None:
        lengths = jnp.full((B,), S_in, jnp.int32)
    # token at absolute position j lives at ring slot j % Sc ("length" stays
    # monotonic; decode masks with min(length, Sc)). Per-row masked scatter:
    # keep the last min(len, Sc) real tokens of each row, drop padding.
    j = jnp.arange(S_in)[None]                       # (1, S_in)
    valid = (j < lengths[:, None]) & (j >= lengths[:, None] - Sc)
    dest = jnp.where(valid, j % Sc, Sc)              # Sc = out-of-range: drop
    bidx = jnp.arange(B)[:, None]
    # advanced-index semantics: result dims (B, S_in) lead, so values are
    # (B, S_in, Hkv[, D]) = cache-layout tensors with S moved ahead of Hkv
    cache["k_q"] = cache["k_q"].at[bidx, :, dest].set(
        jnp.swapaxes(k_q, 1, 2), mode="drop")
    cache["v_q"] = cache["v_q"].at[bidx, :, dest].set(
        jnp.swapaxes(v_q, 1, 2), mode="drop")
    cache["s_k"] = cache["s_k"].at[bidx, :, dest].set(
        jnp.swapaxes(s_k, 1, 2), mode="drop")
    cache["s_v"] = cache["s_v"].at[bidx, :, dest].set(
        jnp.swapaxes(s_v, 1, 2), mode="drop")
    cache["length"] = lengths.astype(jnp.int32)
    return y, cache


def _paginate_kv(k_q, v_q, s_k, s_v, page_size: int) -> Dict:
    """Cache-layout K/V (B, Hkv, S, D) + scales (B, Hkv, S) -> block shape
    (B, nb, Hkv, page_size, D) / (B, nb, Hkv, page_size); the trailing
    partial block is zero-padded (masked by ``length`` at read, overwritten
    in place by decode)."""
    B, Hkv, S = k_q.shape[0], k_q.shape[1], k_q.shape[2]
    nb = -(-S // page_size)
    pad = nb * page_size - S

    def blk(x):
        widths = ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3)
        xp = jnp.pad(x, widths)
        xp = xp.reshape((B, Hkv, nb, page_size) + x.shape[3:])
        return jnp.moveaxis(xp, 2, 1)                # (B, nb, Hkv, bs, ...)

    return {"k_q": blk(k_q), "v_q": blk(v_q),
            "s_k": blk(s_k), "s_v": blk(s_v)}


def _blank_attn_cache(B: int, cfg: ModelConfig, S: int, qdtype=jnp.int8):
    hd = cfg.resolved_head_dim
    return {
        "k_q": jnp.zeros((B, cfg.n_kv_heads, S, hd), qdtype),
        "v_q": jnp.zeros((B, cfg.n_kv_heads, S, hd), qdtype),
        "s_k": jnp.zeros((B, cfg.n_kv_heads, S), jnp.float32),
        "s_v": jnp.zeros((B, cfg.n_kv_heads, S), jnp.float32),
        "length": jnp.zeros((B,), jnp.int32),
    }


def init_attn_cache(cfg: ModelConfig, B: int, S: int, *, window: int = 0,
                    dtype=jnp.int8):
    """window > 0 -> ring buffer bounded at window size (SWA decode)."""
    Sc = min(S, window) if window else S
    return _blank_attn_cache(B, cfg, Sc, dtype)


def init_paged_attn_cache(cfg: ModelConfig, B: int, num_blocks: int,
                          page_size: int, dtype=jnp.int8):
    """Global block pool for one attention layer: ``num_blocks`` blocks of
    ``page_size`` tokens, shared by every slot through the block table."""
    hd = cfg.resolved_head_dim
    return {
        "k_q": jnp.zeros((num_blocks, cfg.n_kv_heads, page_size, hd), dtype),
        "v_q": jnp.zeros((num_blocks, cfg.n_kv_heads, page_size, hd), dtype),
        "s_k": jnp.zeros((num_blocks, cfg.n_kv_heads, page_size),
                         jnp.float32),
        "s_v": jnp.zeros((num_blocks, cfg.n_kv_heads, page_size),
                         jnp.float32),
        "length": jnp.zeros((B,), jnp.int32),
    }


def attn_decode(cfg: ModelConfig, ctx: QuantCtx, p: Dict, x1: jnp.ndarray,
                cache: Dict, positions: jnp.ndarray, *, window: int = 0,
                cross: bool = False,
                block_tbl: Optional[jnp.ndarray] = None):
    """One-token decode step. x1: (B, 1, d). Returns (y1, new_cache).

    Self-attention writes the new K/V into the (ring-buffered when SWA)
    int cache; cross-attention reads a frozen cache. ``block_tbl`` (B, T)
    switches the layer to the paged layout: the commit is routed through
    the slot's block table into the global pool (slots whose table entry is
    the out-of-range sentinel scatter nothing — that is how the engine
    parks finished slots), and attention walks the table instead of a
    contiguous stripe.
    """
    from repro.models.common import rope_tables  # local to avoid cycle
    B = x1.shape[0]
    hd = cfg.resolved_head_dim
    if cross:
        q = qlinear(ctx, x1, p["wq"]).reshape(B, 1, cfg.n_heads, hd)
        q = quantize_act(ctx, q, p, "s_q")
        out = _decode_attn(
            q[:, 0], cache["k_q"], cache["v_q"], cache["s_k"], cache["s_v"],
            cache["length"], mesh=ctx.mesh)
        y = qlinear(ctx, out.reshape(B, 1, cfg.q_dim)[:, 0], p["wo"])
        return y[:, None], cache
    rope = None
    if cfg.rope_theta:
        rope = rope_tables(positions[:, None], hd, cfg.rope_theta)
    q, k, v = _qkv(cfg, ctx, p, x1, x1, rope, None)
    k_q1, v_q1, s_k1, s_v1 = quantize_kv_for_cache(ctx, p, k, v)
    if block_tbl is not None:
        if window:
            raise ValueError("paged cache layout requires full attention "
                             "(window == 0)")
        bs = cache["k_q"].shape[2]
        T = block_tbl.shape[1]
        pos = cache["length"]                        # tokens written so far
        blk = jnp.take_along_axis(
            block_tbl, jnp.minimum(pos // bs, T - 1)[:, None], axis=1)[:, 0]
        off = pos % bs
        new = dict(cache)
        # blk (B,) / off (B,) advanced indices around the head slice ->
        # (B, Hkv, ...) result rows; sentinel blk drops the whole commit
        new["k_q"] = cache["k_q"].at[blk, :, off].set(k_q1[:, :, 0],
                                                      mode="drop")
        new["v_q"] = cache["v_q"].at[blk, :, off].set(v_q1[:, :, 0],
                                                      mode="drop")
        new["s_k"] = cache["s_k"].at[blk, :, off].set(s_k1[:, :, 0],
                                                      mode="drop")
        new["s_v"] = cache["s_v"].at[blk, :, off].set(s_v1[:, :, 0],
                                                      mode="drop")
        new["length"] = pos + 1
        out = _decode_attn_paged(q[:, 0], new["k_q"], new["v_q"],
                                 new["s_k"], new["s_v"], block_tbl,
                                 new["length"], mesh=ctx.mesh)
        y = qlinear(ctx, out.reshape(B, cfg.q_dim), p["wo"])
        return y[:, None], new
    Sc = cache["k_q"].shape[2]
    slot = cache["length"] % Sc            # ring slot (== length pre-wrap)
    bidx = jnp.arange(B)
    new = dict(cache)
    new["k_q"] = cache["k_q"].at[bidx, :, slot].set(k_q1[:, :, 0])
    new["v_q"] = cache["v_q"].at[bidx, :, slot].set(v_q1[:, :, 0])
    new["s_k"] = cache["s_k"].at[bidx, :, slot].set(s_k1[:, :, 0])
    new["s_v"] = cache["s_v"].at[bidx, :, slot].set(s_v1[:, :, 0])
    new["length"] = cache["length"] + 1
    out = _decode_attn(
        q[:, 0], new["k_q"], new["v_q"], new["s_k"], new["s_v"],
        jnp.minimum(new["length"], Sc), mesh=ctx.mesh)
    y = qlinear(ctx, out.reshape(B, cfg.q_dim), p["wo"])
    return y[:, None], new


def attn_chunk_prefill(cfg: ModelConfig, ctx: QuantCtx, p: Dict,
                       x: jnp.ndarray, rope, cache: Dict,
                       tbl: jnp.ndarray, slot: jnp.ndarray,
                       offset: jnp.ndarray, chunk_len: jnp.ndarray):
    """One fixed-size window of an incremental (chunked / tail) prefill
    for a *batch* of slots with per-row offsets.

    x (n, C, d): each row is a window of one slot's prompt whose first
    token sits at absolute position ``offset[i]``; only the first
    ``chunk_len[i]`` positions are real (windows are right-padded, and
    whole padding rows carry ``chunk_len == 0``). Queries attend to the
    ``offset[i]`` tokens already committed to the pool (gathered through
    the row's table ``tbl[i]`` and dequantized at read, like decode) plus
    the window itself (causal, exact bf16 K/V). The window's K/V are
    quantized and scattered through the table with per-row write offsets
    (``kernels.kvq_attn.ops.commit_chunk_kv``), appending blocks the
    allocator grew for each row's window.

    Prefix sharing rides on this contract unchanged: for a prefix-hit
    admission ``offset[i]`` is the cached-token count, so the "history" is
    another request's blocks mapped into ``tbl[i]`` (refcounted by the
    allocator) — including a shared *split block* the offset may point
    into mid-block. The engine resolves copy-on-write for every shared
    block in each row's write range [offset, offset + chunk_len) before
    calling, so the scatter only ever lands in blocks the row's slot
    exclusively owns; the history mask (``kpos < offset``) keeps reads
    inside the shared extent. Rows are mutually independent — a batched
    wave computes exactly what the same windows would serially.

    Note: history keys are read back *quantized*, so a chunked/tail
    prefill is numerically the serving-cache path, not bit-identical to a
    one-shot prefill — same contract as any PagedAttention-style chunked
    prefill over a quantized cache.
    """
    from repro.kernels.kvq_attn.ops import (commit_chunk_kv,
                                            gather_dequant_paged_kv)
    B, C, _ = x.shape                                 # B = slot-batch n
    q, k, v = _qkv(cfg, ctx, p, x, x, rope, None)
    bs = cache["k_q"].shape[2]
    T = tbl.shape[1]
    Lh = T * bs
    # dequantized history, head-major (n, Hkv, Lh, D) -> seq-major; on TPU
    # a fused Pallas gather-dequant walks each row's table (no int8
    # intermediate in HBM), elsewhere the two-gather XLA reference
    kh = gather_dequant_paged_kv(cache["k_q"], cache["s_k"], tbl,
                                 mesh=ctx.mesh)
    vh = gather_dequant_paged_kv(cache["v_q"], cache["s_v"], tbl,
                                 mesh=ctx.mesh)
    kh = jnp.swapaxes(kh, 1, 2)
    vh = jnp.swapaxes(vh, 1, 2)
    kall = jnp.concatenate([kh, k.astype(jnp.float32)], axis=1)
    vall = jnp.concatenate([vh, v.astype(jnp.float32)], axis=1)
    group = cfg.n_heads // cfg.n_kv_heads
    if group > 1:
        kall = jnp.repeat(kall, group, axis=2)
        vall = jnp.repeat(vall, group, axis=2)
    scale = cfg.resolved_head_dim ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32) * scale,
                        kall)
    # key j < Lh is history (valid iff j < offset[row]: allocated-but-
    # unwritten tail positions hold garbage); key j >= Lh is chunk token
    # j - Lh (causal within the chunk, pad keys beyond chunk_len masked)
    kj = jnp.arange(Lh + C)
    qi = jnp.arange(C)
    hist = kj < Lh
    kpos = jnp.where(hist, kj, kj - Lh)
    mask = jnp.where(hist[None, None, :],
                     kpos[None, None, :] < offset[:, None, None],
                     (kpos[None, None, :] <= qi[None, :, None])
                     & (kpos[None, None, :] < chunk_len[:, None, None]))
    scores = jnp.where(mask[:, :, None, :], scores, -1e30)
    pr = jax.nn.softmax(scores, axis=-1)
    pr = jnp.where(mask[:, :, None, :], pr, 0.0)
    out = jnp.einsum("bqhk,bkhd->bqhd", pr, vall)
    y = qlinear(ctx, out.reshape(B, C, cfg.q_dim).astype(x.dtype), p["wo"])
    # commit every row's window through its table (per-row write offsets)
    k_q1, v_q1, s_k1, s_v1 = quantize_kv_for_cache(ctx, p, k, v)
    new = commit_chunk_kv(cache, k_q1, v_q1, s_k1, s_v1, tbl, offset,
                          chunk_len)
    new["length"] = cache["length"].at[slot].set(offset + chunk_len,
                                                 mode="drop")
    return y, new


def attn_spec_verify(cfg: ModelConfig, ctx: QuantCtx, p: Dict,
                     x: jnp.ndarray, rope, cache: Dict,
                     tbl: jnp.ndarray, slot: jnp.ndarray,
                     offset: jnp.ndarray, chunk_len: jnp.ndarray):
    """One attention layer of the speculative *verify-wave*.

    Same per-row ``(offset, chunk_len)`` batched-window contract as
    :func:`attn_chunk_prefill` — x (n, C, d) holds one slot's window
    ``[last_token, draft_1..draft_k]`` per row, committed through the
    block table with per-row write offsets (``commit_chunk_kv``) — but
    the attention *numerics are plain decode's, not prefill's*: the
    window K/V are committed to the pool FIRST (quantized) and every
    window position then reads the pool back dequantized, exactly as the
    ``k + 1`` sequential decode steps it replaces would. Window position
    j attends to ``offset + j + 1`` tokens (history + window through
    itself); positions at or beyond ``chunk_len`` commit nothing (their
    reads are garbage and the engine's acceptance mask discards them).
    Rejected-suffix commits are *rolled back by the caller* (device
    length/position reset + ``BlockAllocator.trim``); the engine must
    have grown the table to ``offset + chunk_len`` tokens and resolved
    copy-on-write for the write range before calling, like any chunk.

    Returns (y (n, C, d), new cache) with ``length`` advanced to the
    full ``offset + chunk_len`` (the engine re-clamps it to the accepted
    extent after acceptance).
    """
    from repro.kernels.kvq_attn.ops import commit_chunk_kv
    B, C, _ = x.shape
    q, k, v = _qkv(cfg, ctx, p, x, x, rope, None)
    k_q1, v_q1, s_k1, s_v1 = quantize_kv_for_cache(ctx, p, k, v)
    new = commit_chunk_kv(cache, k_q1, v_q1, s_k1, s_v1, tbl, offset,
                          chunk_len)
    new["length"] = cache["length"].at[slot].set(offset + chunk_len,
                                                 mode="drop")
    # per-query valid extent: history + the window prefix through itself
    lens = offset[:, None] + 1 + jnp.arange(C)[None]
    out = _spec_verify_attn(q, new["k_q"], new["v_q"], new["s_k"],
                            new["s_v"], tbl, lens, mesh=ctx.mesh)
    y = qlinear(ctx, out.reshape(B, C, cfg.q_dim).astype(x.dtype), p["wo"])
    return y, new
