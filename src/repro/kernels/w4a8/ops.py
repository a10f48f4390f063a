"""jit'd wrapper for the w4a8 matmul kernel: the deployed quantized linear.

``w4a8_linear(x, exported)`` takes bf16 activations, quantizes them per-token
to int8 on the fly (token-dynamic A8d deployment), and runs the packed-int4
matmul. ``exported`` is the dict from ``repro.core.qat.export_linear_int``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantizer import dynamic_quantize_to_int
from repro.kernels import per_device
from repro.kernels.w4a8 import kernel as K
from repro.kernels.w4a8.ref import w4a8_matmul_ref

_INTERPRET = jax.default_backend() != "tpu"


def _pad_to(a, mults):
    pads = [(0, (-d) % m) for d, m in zip(a.shape, mults)]
    return jnp.pad(a, pads) if any(p for _, p in pads) else a


def w4a8_matmul(x_q, w_packed, s_x, s_w, bias=None, out_dtype=jnp.bfloat16,
                use_pallas: bool = True, w_unpacked=None, mesh=None):
    """Tile-padding wrapper. x_q (M,K) int8, w_packed (N,K/2) uint8,
    s_x (M,1), s_w (N,). ``w_unpacked`` is the optional pre-unpacked
    (K, N) int8 plane for the ref backend (see
    ``qat.attach_w4a8_ref_planes``); the Pallas path ignores it. On a
    ``mesh`` the kernel runs whole on every device (operands gathered)."""
    if not use_pallas:
        return w4a8_matmul_ref(x_q, w_packed, s_x, s_w, bias, out_dtype,
                               w_unpacked=w_unpacked)
    run = functools.partial(_pallas_matmul, out_dtype=out_dtype)
    return per_device(run, mesh, P())(x_q, w_packed, s_x, s_w, bias)


def _pallas_matmul(x_q, w_packed, s_x, s_w, bias, out_dtype):
    M = x_q.shape[0]
    N = w_packed.shape[0]
    xp = _pad_to(x_q, (K.BM, K.BK))
    wp = _pad_to(w_packed, (K.BN, K.BK // 2))
    # byte j of a packed row holds input channels 2j and 2j+1: hand the
    # kernel the matching even/odd activation planes
    xe, xo = xp[:, 0::2], xp[:, 1::2]
    sxp = _pad_to(s_x.reshape(M, 1).astype(jnp.float32), (K.BM, 1))
    swp = _pad_to(s_w.reshape(1, N).astype(jnp.float32), (1, K.BN))
    bp = None
    if bias is not None:
        bp = _pad_to(bias.reshape(1, N).astype(jnp.float32), (1, K.BN))
    out = K.w4a8_matmul(xe, xo, wp, sxp, swp, bp, out_dtype=out_dtype,
                        interpret=_INTERPRET)
    return out[:M, :N]


def w4a8_linear(x: jnp.ndarray, exported: dict,
                out_dtype=jnp.bfloat16, use_pallas: bool = True,
                mesh=None) -> jnp.ndarray:
    """Deployed quantized linear over arbitrary leading dims."""
    assert exported.get("packed", True), "w4a8_linear needs packed int4 weights"
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x_q, s_x = dynamic_quantize_to_int(x2, 8, axis=-1)
    y = w4a8_matmul(x_q, exported["wq"], s_x, exported["s_w"].reshape(-1),
                    exported.get("b"), out_dtype, use_pallas,
                    w_unpacked=exported.get("wf"), mesh=mesh)
    return y.reshape(*lead, -1)
