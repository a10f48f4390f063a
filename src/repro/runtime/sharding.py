"""Per-architecture sharding rules (DP / TP / EP / SP on the named mesh).

Mesh axes: ``("data", "model")`` single-pod 16x16, ``("pod", "data",
"model")`` multi-pod 2x16x16. The pod axis is an outer data-parallel axis
(batch shards over ("pod", "data")).

Parameter rules (path-keyed, divisibility-checked — a rule that does not
divide falls back to replication, never to a compile error):

* column-parallel (output over "model"): wq/wk/wv, wg/wu, w1, w_in, w_gate,
  w_ig, w_rg, w_up, w_x, r_h, w_q/w_k/w_v (mLSTM)
* row-parallel (input over "model"): wo, wd, w2, w_out, w_down
* embeddings: vocab over "model" when divisible, else d_model
* MoE: expert-parallel (experts over "model") when n_experts divides the
  axis — the moonshot-64e case; tensor-parallel inside experts otherwise
  (mixtral-8e on a 16-way axis); router replicated
* per-channel quantizer scales follow their weight's output sharding;
  per-tensor scales, norms and the recurrence diagonal replicate
* the Mamba-2 mixer replicates whole (its in_proj output concatenates
  z, x|B|C and dt, which no single column split keeps per head)
* w4a8 export planes (``<linear>/w4a8/{wq,s_w,b,wf}``) shard like the
  linear they shadow: column-parallel owners split ``wq`` on d_out and
  ``s_w``/``b``/``wf`` on the output channel; row-parallel owners split
  ``wq`` on the packed d_in/2 axis (nibble pairs pack adjacent input
  channels, so a divisible packed axis cuts between pairs) and ``wf`` on
  d_in, with ``s_w``/``b`` replicated
* anything under ``segments/`` gets a leading None for the scan axis

Serving rules (``serve_cache_spec`` / ``serve_state_shardings``): only the
quantized KV payload shards — over "model" on the KV-head dim, so GQA
groups stay device-local and the grouped decode grid survives unchanged
per shard. Block tables, positions, lengths, sampling state and token
buffers replicate: the host ``BlockAllocator`` keeps dealing in global
block ids with zero API change.

Batch rules: global batch over ("pod","data"); sequence over "data" when the
batch dim cannot shard (long_500k, batch=1 -> sequence parallelism for the
cache).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

COL_PARALLEL = {"wq", "wk", "wv", "wg", "wu", "w1", "w_in", "w_gate",
                "w_ig", "w_rg", "w_up", "w_x", "r_h", "w_q", "w_k", "w_v"}
ROW_PARALLEL = {"wo", "wd", "w2", "w_out", "w_down"}
MOE_KEYS = {"wg", "wu", "wd"}


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _divides(n: int, by: int) -> bool:
    return by > 0 and n % by == 0


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _maybe(spec_dim: Optional[str], size: int, mesh: Mesh):
    """Use the axis only if it divides the dim."""
    if spec_dim is None:
        return None
    ax = mesh.shape[spec_dim] if isinstance(spec_dim, str) else \
        int(np.prod([mesh.shape[a] for a in spec_dim]))
    return spec_dim if _divides(size, ax) else None


def param_spec(cfg: ModelConfig, mesh: Mesh, path: str,
               shape: Tuple[int, ...]) -> P:
    """PartitionSpec for one parameter leaf."""
    parts = path.split("/")
    key = parts[-1]
    parent = parts[-2] if len(parts) >= 2 else ""
    in_scan = "segments" in parts
    is_moe = len(parts) >= 3 and "moe" in parts
    m = mesh.shape["model"]

    def lead(spec: P) -> P:
        # scan-stacked params carry a leading layer axis (replicated)
        if in_scan and len(spec) < len(shape):
            return P(*((None,) * (len(shape) - len(spec)) + tuple(spec)))
        return spec

    # ---- w4a8 export planes (serve-time packed weights) -------------------
    # Must precede the head branch: head/w4a8/wq has parts[-2] == "w4a8".
    if "w4a8" in parts:
        owner = parts[parts.index("w4a8") - 1] if parts.index("w4a8") else ""
        col = owner in COL_PARALLEL or owner == "head"
        row = owner in ROW_PARALLEL
        if key == "wq":                 # packed uint8 (d_out, d_in/2)
            if col:
                return lead(P(_maybe("model", shape[-2], mesh), None))
            if row:
                return lead(P(None, _maybe("model", shape[-1], mesh)))
            return lead(P(None, None))
        if key == "wf":                 # int8 ref plane (d_in, d_out)
            if col:
                return lead(P(None, _maybe("model", shape[-1], mesh)))
            if row:
                return lead(P(_maybe("model", shape[-2], mesh), None))
            return lead(P(None, None))
        if key == "s_w":                # (1, d_out): follows output sharding
            if col:
                return lead(P(None, _maybe("model", shape[-1], mesh)))
            return lead(P(None, None))
        if key == "b":
            return lead(P(_maybe("model", shape[-1], mesh)) if col
                        else P(None))
        return lead(P())

    # ---- embeddings / head ------------------------------------------------
    if path.endswith("embed/w"):        # (V, d) or (maxpos, d)
        if parts[-2] == "embed" and _divides(shape[0], m) \
                and "pos_embed" not in path:
            return P("model", None)
        return P(None, _maybe("model", shape[-1], mesh))
    if parts[0] == "head" or (len(parts) >= 2 and parts[-2] == "head"):
        if key == "w":                  # (d, V)
            return P(None, _maybe("model", shape[-1], mesh))
        if key == "s_w":                # (1, V)
            return P(None, _maybe("model", shape[-1], mesh))
        return P()

    # ---- MoE expert tensors ------------------------------------------------
    if is_moe and parent in MOE_KEYS and key in ("w", "s_w"):
        e = shape[1] if in_scan else shape[0]
        base = len(shape) - 3           # dims before (E, din, dout)
        if _divides(e, m):              # expert parallelism
            return P(*((None,) * base + ("model", None, None)))
        if parent in ("wg", "wu"):      # TP inside experts, column
            return P(*((None,) * base + (None, None, "model"))) \
                if key == "w" else \
                P(*((None,) * base + (None, None, "model")))
        return P(*((None,) * base + (None, "model", None))) \
            if key == "w" else P(*((None,) * base + (None, None, None)))

    # ---- quantizer scales ----------------------------------------------------
    if key == "s_w":                    # (1, dout) [+ scan lead]
        if parent in COL_PARALLEL and _divides(shape[-1], m):
            return lead(P(None, "model"))
        return lead(P(None, None))
    if key.startswith("s_"):            # per-tensor scalars
        return lead(P())

    # ---- Mamba-2 mixer: replicated. Its in_proj output concatenates z,
    # x|B|C and dt, so no one column split keeps a head's streams together
    if "mamba" in parts:
        return P()

    # ---- linears ----------------------------------------------------------------
    if key == "w" and parent in COL_PARALLEL:
        return lead(P(None, _maybe("model", shape[-1], mesh)))
    if key == "w" and parent in ROW_PARALLEL:
        return lead(P(_maybe("model", shape[-2], mesh), None))
    if key == "b":
        if parent in COL_PARALLEL:
            return lead(P(_maybe("model", shape[-1], mesh)))
        return lead(P(None))

    # ---- recurrent diagonals / conv ----------------------------------------------
    if key in ("lam", "conv_b"):
        return lead(P(_maybe("model", shape[-1], mesh)))
    if key == "conv_w":
        return lead(P(None, _maybe("model", shape[-1], mesh)))

    # ---- norms, router, gates, everything else: replicated ----------------------
    return lead(P(*([None] * 0)))


def param_shardings(cfg: ModelConfig, mesh: Mesh, params_shapes) -> Any:
    """NamedSharding tree matching a params (shape) pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    out = []
    for path, leaf in flat:
        spec = param_spec(cfg, mesh, _path_str(path), leaf.shape)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# Batch / cache shardings
# --------------------------------------------------------------------------

def batch_spec(mesh: Mesh, shape: Tuple[int, ...], name: str) -> P:
    dp = batch_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    if name == "positions":             # (3, B, S)
        if len(shape) >= 2 and _divides(shape[1], dp_size):
            return P(None, dp)
        return P()
    if not shape:
        return P()
    if _divides(shape[0], dp_size):
        return P(*((dp,) + (None,) * (len(shape) - 1)))
    # batch unshardable (e.g. long_500k B=1): sequence parallelism over data
    if len(shape) >= 2 and _divides(shape[1], mesh.shape["data"]):
        return P(None, "data")
    return P()


def batch_shardings(mesh: Mesh, batch_shapes: Dict[str, Any]) -> Dict:
    return {k: NamedSharding(mesh, batch_spec(mesh, v.shape, k))
            for k, v in batch_shapes.items()}


def cache_spec(cfg: ModelConfig, mesh: Mesh, path: str,
               shape: Tuple[int, ...]) -> P:
    """Serving-cache leaf sharding.

    Attention caches (rep, B, Hkv, S, D): batch over DP when divisible,
    else sequence over "data" (long-context SP); kv-heads over "model" when
    divisible, else head_dim. Recurrent states: width/heads over "model".
    """
    key = path.split("/")[-1]
    dp = batch_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    m = mesh.shape["model"]
    if key in ("length", "position"):
        return P()
    has_rep = "segments" in path
    base = 1 if has_rep else 0          # leading scan axis replicated
    dims: list = [None] * len(shape)
    bdim = base
    if len(shape) > bdim and _divides(shape[bdim], dp_size):
        dims[bdim] = dp
        seq_sharded = False
    else:
        seq_sharded = True
    if key in ("k_q", "v_q"):           # (..., B, Hkv, S, D)
        hkv, S, D = shape[-3], shape[-2], shape[-1]
        if _divides(hkv, m):
            dims[-3] = "model"
        elif _divides(S, m):
            # context parallelism: shard the cache sequence over "model"
            # (head_dim sharding would all-reduce every decode score tile)
            dims[-2] = "model"
        elif _divides(D, m):
            dims[-1] = "model"
        if seq_sharded and dims[-2] is None \
                and _divides(S, mesh.shape["data"]):
            dims[-2] = "data"
    elif key in ("s_k", "s_v"):         # (..., B, Hkv, S)
        hkv, S = shape[-2], shape[-1]
        if _divides(hkv, m):
            dims[-2] = "model"
        elif _divides(S, m):
            dims[-1] = "model"
        elif seq_sharded and _divides(S, mesh.shape["data"]):
            dims[-1] = "data"
    elif key in ("state_q", "conv_buf", "c"):
        if _divides(shape[-1], m):
            dims[-1] = "model"
        elif len(shape) >= 3 and _divides(shape[-3], m):
            dims[-3] = "model"
    elif key == "s_state":
        pass                             # small scales: replicated
    return P(*dims)


def cache_shardings(cfg: ModelConfig, mesh: Mesh, cache_shapes) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    out = []
    for path, leaf in flat:
        spec = cache_spec(cfg, mesh, _path_str(path), leaf.shape)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# Serving-engine shardings (paged pool + full device state pytree)
# --------------------------------------------------------------------------

def serve_cache_spec(cfg: ModelConfig, mesh: Mesh, path: str,
                     shape: Tuple[int, ...]) -> P:
    """Serve-cache leaf sharding (paged block pool or dense per-slot).

    Unlike the training ``cache_spec``, the leading pool axis is NEVER
    sharded: for a paged pool that axis is the global block-id space the
    host allocator indexes into, and splitting it over "data" would turn
    every block-table lookup into a cross-device gather. Only the KV-head
    dim shards (over "model", when divisible) so attention stays
    head-local per device; everything else — lengths, positions, block
    tables, recurrent state — replicates.
    """
    key = path.split("/")[-1]
    m = mesh.shape["model"]
    dims: list = [None] * len(shape)
    if key in ("k_q", "v_q") and len(shape) >= 4:   # (..., NB|B, Hkv, S, D)
        if _divides(shape[-3], m):
            dims[-3] = "model"
    elif key in ("s_k", "s_v") and len(shape) >= 3:  # (..., NB|B, Hkv, S)
        if _divides(shape[-2], m):
            dims[-2] = "model"
    return P(*dims)


def serve_cache_shardings(cfg: ModelConfig, mesh: Mesh, cache) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for path, leaf in flat:
        spec = serve_cache_spec(cfg, mesh, _path_str(path), leaf.shape)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def serve_state_shardings(cfg: ModelConfig, mesh: Mesh, state) -> Any:
    """Shardings for the engine's full device state pytree.

    The cache subtree follows ``serve_cache_spec``; sampling state, token
    buffers, RNG keys and per-slot bookkeeping replicate (they are tiny
    and the sampler all-gathers the sharded logits anyway).
    """
    rep = NamedSharding(mesh, P())
    return {k: (serve_cache_shardings(cfg, mesh, v) if k == "cache"
                else jax.tree.map(lambda _: rep, v))
            for k, v in state.items()}


def opt_shardings(param_sh: Any, opt_state_shapes) -> Any:
    """Optimizer moments shard exactly like their parameters."""
    from repro.optim.adamw import AdamWState
    return AdamWState(
        step=NamedSharding(list(jax.tree.leaves(param_sh))[0].mesh, P()),
        m=param_sh, v=jax.tree.map(lambda s: s, param_sh))
