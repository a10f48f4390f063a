"""Pallas TPU kernels for the paper's quantized hot spots. Each package
holds ``kernel.py`` (the Pallas call), ``ref.py`` (its pure-jnp oracle) and
``ops.py`` (the jit-side wrapper that pads, lays out and dispatches)."""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def head_axis(mesh, n_kv_heads: int):
    """Mesh axis the KV-head dim is split over inside a sharded program:
    "model" where it divides the head count (the serve pool's own rule),
    else None (replicated)."""
    if mesh is None or "model" not in mesh.axis_names:
        return None
    return "model" if n_kv_heads % mesh.shape["model"] == 0 else None


def per_device(fn, mesh, in_specs, out_specs=P()):
    """``fn`` as a per-device program of ``mesh`` (identity off a mesh).

    GSPMD cannot partition a Pallas call, so inside a sharded program each
    kernel runs under a shard_map: operands arrive as the given specs'
    local blocks (replicated specs gather them whole) and every device
    runs the kernel on its block.
    """
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
