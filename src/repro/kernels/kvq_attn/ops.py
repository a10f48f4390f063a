"""jit-side wrappers for the quantized-KV kernels: dense and paged
flash-decode, spec-verify, gather-dequant and the pool's block copy. They
pad and regroup operands into the kernels' layouts, and on a serving mesh
run each kernel per device."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import head_axis, per_device
from repro.kernels.kvq_attn import kernel as K
from repro.kernels.kvq_attn.ref import (chunk_commit_ids, copy_pool_blocks_ref,
                                        gather_paged_kv,
                                        kvq_decode_attn_ref,
                                        kvq_paged_decode_attn_ref,
                                        kvq_spec_verify_attn_ref,
                                        scatter_chunk_kv)

_INTERPRET = jax.default_backend() != "tpu"


def commit_chunk_kv(cache: dict, k_q, v_q, s_k, s_v, block_tbl,
                    offset, chunk_len) -> dict:
    """Commit a batch of prefill windows into one layer's block pool, with
    per-row write offsets.

    cache: layer dict holding pool leaves k_q/v_q (NB, Hkv, bs, D) and
    s_k/s_v (NB, Hkv, bs). k_q/v_q values (n, Hkv, C, D) int, s_k/s_v
    (n, Hkv, C) fp32: the quantized window K/V of ``n`` slots, each
    starting at absolute token position ``offset[i]`` with ``chunk_len[i]``
    real tokens. block_tbl (n, T): each row's (truncated) block table.
    Destinations are resolved once (`chunk_commit_ids`) and shared by the
    four leaf scatters; pad rows/positions land on the sentinel and drop.
    XLA's batched scatter is already memory-bound-optimal here, so the
    same path serves every backend (a Pallas variant would only re-tile
    the identical HBM traffic).
    """
    bs = cache["k_q"].shape[2]
    nb = cache["k_q"].shape[0]
    blk, off = chunk_commit_ids(block_tbl, offset, chunk_len, k_q.shape[2],
                                bs, nb)
    new = dict(cache)
    new["k_q"] = scatter_chunk_kv(cache["k_q"], jnp.swapaxes(k_q, 1, 2),
                                  blk, off)
    new["v_q"] = scatter_chunk_kv(cache["v_q"], jnp.swapaxes(v_q, 1, 2),
                                  blk, off)
    new["s_k"] = scatter_chunk_kv(cache["s_k"], jnp.swapaxes(s_k, 1, 2),
                                  blk, off)
    new["s_v"] = scatter_chunk_kv(cache["s_v"], jnp.swapaxes(s_v, 1, 2),
                                  blk, off)
    return new


def copy_pool_blocks(pool, src, dst, use_pallas: Optional[bool] = None,
                     mesh=None) -> jnp.ndarray:
    """Device-side copy-on-write block clone over a layer-stacked pool leaf.

    pool (rep, NB, ...) int8 payload or fp32 scales; src/dst (n,) int32
    block-id pairs. ``dst`` entries >= NB are padding (the engine buckets
    the pair count to a power of two to bound compile variants) and are
    dropped. On TPU the Pallas kernel rewrites only the ``dst`` blocks via
    an aliased in-place pallas_call; elsewhere the XLA scatter reference
    runs (bitwise-identical result). On a ``mesh`` each device copies the
    blocks of its own KV-head shard.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return copy_pool_blocks_ref(pool, src, dst)
    nb = pool.shape[1]
    pad = dst >= nb
    # padding convention for the kernel: src == dst is a self-copy no-op.
    # Pads self-copy the first *source* block — a src is never a dst in
    # the same call, so no pad step can race a real pair's output DMA
    # (self-copying a dst block could prefetch its stale payload and
    # write it back after the real copy landed).
    srcp = jnp.where(pad, src[0], src).astype(jnp.int32)
    dstp = jnp.where(pad, src[0], dst).astype(jnp.int32)
    spec = P(None, None, head_axis(mesh, pool.shape[2]))
    copy = functools.partial(K.pool_block_copy, interpret=_INTERPRET)
    return per_device(copy, mesh, (spec, P(), P()), spec)(pool, srcp, dstp)


def gather_dequant_paged_kv(pool, s_pool, block_tbl,
                            use_pallas: Optional[bool] = None,
                            mesh=None) -> jnp.ndarray:
    """Dequantized history gather for the batched tail/chunk prefill wave.

    pool (NB, Hkv, bs, D) int8; s_pool (NB, Hkv, bs) fp32; block_tbl (n, T)
    int32 (sentinels clamped here). Returns (n, Hkv, T*bs, D) f32. On TPU
    the fused Pallas kernel dequantizes each gathered tile VMEM-locally (no
    int8 intermediate in HBM); elsewhere the two-gather XLA reference runs
    — bitwise-identical values either way.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return (gather_paged_kv(pool, block_tbl).astype(jnp.float32)
                * gather_paged_kv(s_pool, block_tbl)[..., None])
    nb = pool.shape[0]
    tbl = jnp.minimum(block_tbl.astype(jnp.int32), nb - 1)
    heads = P(None, head_axis(mesh, pool.shape[1]))
    gather = functools.partial(K.gather_dequant_paged_kv,
                               interpret=_INTERPRET)
    return per_device(gather, mesh, (heads, heads, P()), heads)(
        pool, s_pool.astype(jnp.float32), tbl)


def _group_rows(q, lengths, Hkv: int):
    """(B, C, H, D) queries with (B, C) valid extents -> the kernels'
    grouped operands q (B, Hkv, R, D) and lengths (B, R, 1).

    The R rows of KV head n are its GQA group's query heads times the C
    window positions (row ``g * C + c`` for head ``n * group + g``),
    padded to a multiple of 8 f32 sublanes. Pad rows have q = 0 and
    length 0, so every position masks out and they reduce to exact zeros
    (no NaN: the final divide clamps the denominator)."""
    B, C, H, D = q.shape
    group = H // Hkv
    R = group * C
    Rp = -(-R // 8) * 8
    qg = q.reshape(B, C, Hkv, group, D).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(B, Hkv, R, D)
    lens = jnp.broadcast_to(lengths.astype(jnp.int32)[:, None, :],
                            (B, group, C)).reshape(B, R, 1)
    if Rp != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
        lens = jnp.pad(lens, ((0, 0), (0, Rp - R), (0, 0)))
    return qg, lens


def _ungroup_rows(out, C: int, H: int):
    """Inverse of :func:`_group_rows` on the kernel output."""
    B, Hkv, _, D = out.shape
    group = H // Hkv
    out = out[:, :, :group * C].reshape(B, Hkv, group, C, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D)


def kvq_decode_attn(q, k_q, v_q, s_k, s_v, lengths,
                    use_pallas: bool = True, mesh=None) -> jnp.ndarray:
    """Decode attention over an integer cache; pads S to tile multiples.

    q (B,H,D); k_q/v_q (B,Hkv,S,D) int8; s_k/s_v (B,Hkv,S) fp32;
    lengths (B,) int32.
    """
    if not use_pallas:
        return kvq_decode_attn_ref(q, k_q, v_q, s_k, s_v, lengths)
    S = k_q.shape[2]
    pad = (-S) % K.BS
    if pad:
        padkv = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_q = jnp.pad(k_q, padkv)
        v_q = jnp.pad(v_q, padkv)
        pads = ((0, 0), (0, 0), (0, pad))
        s_k = jnp.pad(s_k, pads)
        s_v = jnp.pad(s_v, pads)

    def run(q, k_q, v_q, s_k, s_v, lengths):
        qg, lens = _group_rows(q[:, None], lengths[:, None], k_q.shape[1])
        out = K.kvq_decode_attn(qg, k_q, v_q, s_k, s_v, lens,
                                interpret=_INTERPRET)
        return _ungroup_rows(out, 1, q.shape[1])[:, 0]

    heads = P(None, head_axis(mesh, k_q.shape[1]))
    return per_device(run, mesh, (heads,) * 5 + (P(),), heads)(
        q, k_q, v_q, s_k.astype(jnp.float32), s_v.astype(jnp.float32),
        lengths)


def _paged_attn(q, k_pool, v_pool, s_k, s_v, block_tbl, lengths, mesh):
    """q (B, C, H, D), lengths (B, C) through the block-table kernel."""
    nb, Hkv, bs = k_pool.shape[:3]
    if not _INTERPRET and bs < 32:
        # an int8 (bs, D) K/V tile must cover the 32-sublane int8 tile
        raise ValueError(f"the paged attention kernel needs block_size >= "
                         f"32 on TPU, got {bs}")

    def run(q, k_pool, v_pool, s_k, s_v, tbl, lengths):
        qg, lens = _group_rows(q, lengths, k_pool.shape[1])
        out = K.kvq_paged_attn(qg, k_pool, v_pool, s_k, s_v, tbl, lens,
                               interpret=_INTERPRET)
        return _ungroup_rows(out, q.shape[1], q.shape[2])

    ax = head_axis(mesh, Hkv)
    pool, qs = P(None, ax), P(None, None, ax)
    return per_device(run, mesh, (qs,) + (pool,) * 4 + (P(), P()), qs)(
        q, k_pool, v_pool, s_k.astype(jnp.float32), s_v.astype(jnp.float32),
        jnp.minimum(block_tbl.astype(jnp.int32), nb - 1), lengths)


def kvq_spec_verify_attn(q, k_pool, v_pool, s_k, s_v, block_tbl, lengths,
                         use_pallas: bool = True, mesh=None) -> jnp.ndarray:
    """Multi-query block-table attention for the speculative verify-wave.

    q (B, C, H, D): the wave's C window queries per slot (their K/V are
    already committed to the pool); block_tbl (B, T) int32 (sentinels
    clamped here); lengths (B, C) per-query valid extents. On TPU the
    block-table kernel serves all C queries in one table walk; elsewhere
    the gather + per-position decode oracle runs (bitwise identical to C
    sequential decode steps).
    """
    if not use_pallas:
        return kvq_spec_verify_attn_ref(q, k_pool, v_pool, s_k, s_v,
                                        block_tbl, lengths)
    return _paged_attn(q, k_pool, v_pool, s_k, s_v, block_tbl, lengths,
                       mesh)


def kvq_paged_decode_attn(q, k_pool, v_pool, s_k, s_v, block_tbl, lengths,
                          use_pallas: bool = True, mesh=None) -> jnp.ndarray:
    """Block-table decode attention over a paged integer cache pool.

    q (B,H,D); k_pool/v_pool (NB,Hkv,bs,D) int8; s_k/s_v (NB,Hkv,bs) fp32;
    block_tbl (B,T) int32 (entries >= NB are unallocated sentinels, clamped
    here); lengths (B,) int32 tokens resident per slot. The verify-wave's
    kernel with a one-query window. On TPU the pool's block size must be
    >= 32 (the int8 tile); interpret mode runs the kernel at any size.
    """
    if not use_pallas:
        return kvq_paged_decode_attn_ref(q, k_pool, v_pool, s_k, s_v,
                                         block_tbl, lengths)
    return _paged_attn(q[:, None], k_pool, v_pool, s_k, s_v, block_tbl,
                       lengths[:, None], mesh)[:, 0]
