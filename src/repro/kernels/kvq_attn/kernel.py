"""Pallas TPU kernels: flash-decode attention over an int8/int4 KV cache,
plus the paged pool's gather-dequant and copy-on-write block copy.

TPU adaptation of the paper's "quantization fused into the attention kernel"
policy (the CUDA flash kernel encapsulates the softmax; our Pallas kernel
encapsulates cache *dequantization*): integer K/V tiles are read straight
into VMEM and their per-token scales applied there, so HBM traffic is 2-4x
lower than a bf16 cache and no dequantized copy ever exists in HBM.

Both attention kernels run the same online-softmax walk (``_kernel``): grid
(B, Hkv, tiles), one step per *KV* head, state (m, l, acc) in VMEM scratch
carried across the cache tiles (innermost grid dim). The q operand arrives
pre-grouped as (B, Hkv, R, D): all R query rows that read one cache head (the
GQA group, times the verify window for speculative decoding) stacked on the
sublane axis, R a multiple of 8 (ops.py pads). Each int8 (bs, D) K/V tile is
therefore fetched once per KV head, and the score / accumulator tiles are
full-sublane (R, bs) / (R, D) VREGs. Per-row valid extents ride along as an
(R, 1) int32 column.

TPU tiling rule: a block's last two dims are multiples of (8, 128) or equal
to the array's. So a scale tile is the block's whole (Hkv, bs) slab and the
kernel reads its head's row, and the lengths column is a whole (R, 1) slab.
The (1, bs) scale row scales the (R, bs) score / probability tiles rather
than the (bs, D) payload, which would need the row moved onto sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

BS = 512  # dense-cache tokens per tile

_NEG = -1e30
_HI = jax.lax.Precision.HIGHEST   # f32 dots stay f32 on the MXU


def _kernel(len_ref, q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, nt: int, scale: float):
    """One cache tile of the online-softmax walk. Tile ``t`` covers
    absolute positions [t*bs, (t+1)*bs) of the row's cache; q row r sees
    positions < ``len_ref[0][r]``."""
    h = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s_k = sk_ref[0, pl.ds(h, 1), :]                       # (1, bs)
    s_v = sv_ref[0, pl.ds(h, 1), :]
    q = q_ref[0, 0].astype(jnp.float32)                   # (R, D)
    scores = jax.lax.dot_general(
        q, k_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=_HI, preferred_element_type=jnp.float32)
    scores = scores * s_k * scale                         # (R, bs)

    pos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < len_ref[0]                              # (R, bs)
    scores = jnp.where(valid, scores, _NEG)

    m_prev = m_ref[...]                                   # (R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new) * valid.astype(jnp.float32)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p * s_v, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=_HI, preferred_element_type=jnp.float32)  # (R, D)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(t == nt - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def _paged_kernel(tbl_ref, *refs, **kw):
    _kernel(*refs, **kw)    # the table only steers the index maps


def _scratch(rows: int, D: int):
    return [pltpu.VMEM((rows, 1), jnp.float32),   # running max
            pltpu.VMEM((rows, 1), jnp.float32),   # running denom
            pltpu.VMEM((rows, D), jnp.float32)]   # output accumulator


def kvq_decode_attn(q, k_q, v_q, s_k, s_v, lengths, interpret: bool = True):
    """Flash-decode over a dense per-slot int cache.

    q (B, Hkv, R, D) pre-grouped; k_q/v_q (B, Hkv, S, D) int8 with S a
    multiple of BS; s_k/s_v (B, Hkv, S) f32; lengths (B, R, 1) int32.
    Returns (B, Hkv, R, D) in q.dtype.
    """
    B, Hkv, R, D = q.shape
    ns = k_q.shape[2] // BS
    kv_ix = lambda b, h, s: (b, h, s, 0)
    sc_ix = lambda b, h, s: (b, 0, s)
    row_ix = lambda b, h, s: (b, h, 0, 0)
    return pl.pallas_call(
        functools.partial(_kernel, bs=BS, nt=ns, scale=1.0 / D ** 0.5),
        grid=(B, Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, R, 1), lambda b, h, s: (b, 0, 0)),  # lengths
            pl.BlockSpec((1, 1, R, D), row_ix),                  # q
            pl.BlockSpec((1, 1, BS, D), kv_ix),                  # k
            pl.BlockSpec((1, 1, BS, D), kv_ix),                  # v
            pl.BlockSpec((1, Hkv, BS), sc_ix),                   # s_k
            pl.BlockSpec((1, Hkv, BS), sc_ix),                   # s_v
        ],
        out_specs=pl.BlockSpec((1, 1, R, D), row_ix),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=_scratch(R, D),
        interpret=interpret,
    )(lengths, q, k_q, v_q, s_k, s_v)


def kvq_paged_attn(q, k_pool, v_pool, s_k, s_v, block_tbl, lengths,
                   interpret: bool = True):
    """Block-table flash attention over a paged int8/int4 KV pool: the
    decode step (one query per slot) and the speculative verify-wave (the
    ``k + 1`` window queries of a slot, in ONE walk of its table).

    The grid's innermost dim walks the slot's *block table* instead of a
    contiguous cache stripe: the table rides in as a scalar-prefetch
    operand so the K/V BlockSpec index maps turn (slot, table index) into
    a pool block id before the tile DMA is issued. Sentinel entries must
    be clamped to NB-1 by the caller (ops.py); their positions are masked
    by ``lengths``.

    q (B, Hkv, R, D) pre-grouped; pools (NB, Hkv, bs, D) int8; scales
    (NB, Hkv, bs) f32; block_tbl (B, T) int32 (clamped); lengths (B, R, 1)
    int32. Returns (B, Hkv, R, D) in q.dtype.
    """
    B, Hkv, R, D = q.shape
    bs = k_pool.shape[2]
    T = block_tbl.shape[1]
    kv_ix = lambda b, h, t, tbl: (tbl[b, t], h, 0, 0)
    sc_ix = lambda b, h, t, tbl: (tbl[b, t], 0, 0)
    row_ix = lambda b, h, t, tbl: (b, h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # block_tbl
        grid=(B, Hkv, T),
        in_specs=[
            pl.BlockSpec((1, R, 1), lambda b, h, t, tbl: (b, 0, 0)),
            pl.BlockSpec((1, 1, R, D), row_ix),      # q
            pl.BlockSpec((1, 1, bs, D), kv_ix),      # k pool
            pl.BlockSpec((1, 1, bs, D), kv_ix),      # v pool
            pl.BlockSpec((1, Hkv, bs), sc_ix),       # s_k pool
            pl.BlockSpec((1, Hkv, bs), sc_ix),       # s_v pool
        ],
        out_specs=pl.BlockSpec((1, 1, R, D), row_ix),
        scratch_shapes=_scratch(R, D),
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, nt=T, scale=1.0 / D ** 0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_tbl, lengths, q, k_pool, v_pool, s_k, s_v)


def _gather_dequant_kernel(tbl_ref, kq_ref, sk_ref, o_ref):
    bs = kq_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1))
    for h in range(kq_ref.shape[1]):
        # the head's (1, bs) scale row as a (bs, 1) column: a masked lane
        # sum with one nonzero term per row, so exact
        col = jnp.sum(jnp.where(eye, sk_ref[0, h:h + 1, :], 0.0), axis=1,
                      keepdims=True)
        o_ref[0, h, 0] = kq_ref[0, h].astype(jnp.float32) * col


def gather_dequant_paged_kv(pool, s_pool, block_tbl, interpret: bool = True):
    """Fused gather + dequant of each row's block-table extent.

    The tail-wave history read: the XLA path gathers the int8 pool and the
    scale pool separately, materializing an int8 copy of every history
    block in HBM before a second dequantize pass re-reads it. Here one
    grid step per (row, table entry) DMAs the block's (Hkv, bs, D) int8
    payload and its (Hkv, bs) scales straight into VMEM and writes only
    the dequantized f32 tiles back — the int8 intermediate never exists in
    HBM. Sentinel table entries must be clamped by the caller (ops.py);
    callers mask their positions exactly as they do for the XLA gather.

    pool (NB, Hkv, bs, D) int8; s_pool (NB, Hkv, bs) f32; block_tbl (n, T)
    int32 (clamped). Returns (n, Hkv, T*bs, D) f32 — identical layout and
    bitwise-identical values to ``gather_paged_kv(pool).astype(f32) *
    gather_paged_kv(s_pool)[..., None]``.
    """
    NB, Hkv, bs, D = pool.shape
    n, T = block_tbl.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # block_tbl
        grid=(n, T),
        in_specs=[
            pl.BlockSpec((1, Hkv, bs, D), lambda r, t, tbl: (tbl[r, t], 0, 0,
                                                             0)),
            pl.BlockSpec((1, Hkv, bs), lambda r, t, tbl: (tbl[r, t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, 1, bs, D),
                               lambda r, t, tbl: (r, 0, t, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, Hkv, T, bs, D), jnp.float32),
        interpret=interpret,
    )(block_tbl, pool, s_pool)
    return out.reshape(n, Hkv, T * bs, D)


def _copy_kernel(src_ref, dst_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...]


def pool_block_copy(x, src, dst, interpret: bool = True):
    """In-place pool-block copy: ``x[:, dst[i]] <- x[:, src[i]]``.

    The copy-on-write primitive of the prefix-shared paged cache: when a
    slot must write into a block another slot still maps, the engine clones
    the int8 payload (+ scales) device-side and repoints the writer's table
    entry at the clone. ``x`` (rep, NB, ...) is a layer-stacked pool leaf;
    a block is everything past the NB axis, so its last two dims are the
    array's. The pool is aliased into the output so only the ``dst``
    blocks are rewritten — one block DMA per (layer, pair) grid step, no
    full-pool traffic. Pairs with ``src == dst`` are self-copy no-ops (the
    padding convention ops.py uses to bound compile variants).
    """
    rep, _nb, *blk = x.shape
    n = src.shape[0]
    tail = (0,) * len(blk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # src ids, dst ids
        grid=(rep, n),
        in_specs=[pl.BlockSpec((1, 1, *blk),
                               lambda r, i, s, d: (r, s[i], *tail))],
        out_specs=pl.BlockSpec((1, 1, *blk),
                               lambda r, i, s, d: (r, d[i], *tail)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={2: 0},                 # pool is updated in place
        interpret=interpret,
    )(src, dst, x)
