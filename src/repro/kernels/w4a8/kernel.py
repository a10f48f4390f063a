"""Pallas TPU kernel: packed-int4-weight x int8-activation matmul.

The deployment (serving) hot path. TPU-native design:
* weights stored HBM-packed (two int4 per byte) -> 2x less HBM traffic than
  int8, 4x less than bf16; nibbles are unpacked in VMEM registers into a
  low (even-channel) and a high (odd-channel) plane, each contracted
  against the matching half of the activations, which arrive pre-split,
* the MXU consumes int8 x int8 -> int32 accumulation
  (``preferred_element_type=int32``),
* per-token activation scale (M, 1) and per-output-channel weight scale (N,)
  are applied once per output tile in the epilogue (VREG broadcasts),
  fused with the optional bias add.

Grid (M/bm, N/bn, K/bk), K innermost for accumulation in VMEM scratch.
Tiles: bm=256, bn=256, bk=512 -> x tiles 2x64 KiB int8, packed w tile 64 KiB,
acc 256 KiB int32; MXU dims all multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

BM, BN, BK = 256, 256, 512


def _unpack_nibbles(p: jnp.ndarray):
    """(n, k/2) packed uint8 -> (lo, hi) int8 planes in [-8, 7].

    ``lo`` holds the even input channels, ``hi`` the odd ones. Shifting the
    nibble to the top of an int32 and back sign-extends it; the planes stay
    (n, k/2), so no vector shape cast is needed to feed the MXU."""
    w = p.astype(jnp.int32)
    lo = (w << 28) >> 28
    hi = (w << 24) >> 28
    return lo.astype(jnp.int8), hi.astype(jnp.int8)


def _kernel(xe_ref, xo_ref, wp_ref, sx_ref, sw_ref, b_ref, o_ref, acc_ref, *,
            nk: int, has_bias: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo, hi = _unpack_nibbles(wp_ref[...])             # (BN, BK/2) int8 each
    nt = (((1,), (1,)), ((), ()))                     # contract K with K
    acc_ref[...] += (
        jax.lax.dot_general(xe_ref[...], lo, nt,
                            preferred_element_type=jnp.int32)
        + jax.lax.dot_general(xo_ref[...], hi, nt,
                              preferred_element_type=jnp.int32))

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32)
        y = y * sx_ref[...].astype(jnp.float32)       # (BM, 1)
        y = y * sw_ref[...].astype(jnp.float32)       # (1, BN)
        if has_bias:
            y = y + b_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


def w4a8_matmul(x_even: jnp.ndarray, x_odd: jnp.ndarray,
                w_packed: jnp.ndarray, s_x: jnp.ndarray, s_w: jnp.ndarray,
                bias: jnp.ndarray | None = None, out_dtype=jnp.bfloat16,
                interpret: bool = True) -> jnp.ndarray:
    """x_even/x_odd: (M, K/2) int8, the even and odd input channels of the
    activations (ops.py splits them); w_packed: (N, K/2) uint8, byte j
    packing channels 2j (low nibble) and 2j+1 (high); s_x: (M, 1);
    s_w: (1, N).

    All dims must be tile multiples (ops.py pads).
    """
    M, Kh = x_even.shape
    N = w_packed.shape[0]
    nk = 2 * Kh // BK
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((1, N), jnp.float32)
    grid = (M // BM, N // BN, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, has_bias=has_bias),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((BM, BK // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((BN, BK // 2), lambda i, j, k: (j, k)),
            pl.BlockSpec((BM, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, BN), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, BN), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.int32)],
        interpret=interpret,
    )(x_even, x_odd, w_packed, s_x, s_w, bias)
