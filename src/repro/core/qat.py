"""QAT integration: quantization context, site helpers, and calibration flow.

Design
------
Quantizer step sizes live *inside* the parameter pytree, under reserved keys
beginning with ``s_`` next to the tensors they quantize::

    linear  = {"w": (d_in, d_out), ["b": (d_out,)],
               "s_w": (1, d_out),          # per-output-channel weight scale
               "s_in": ()}                 # per-tensor activation scale
    attn    = {... , "s_q": (), "s_k": (), "s_v": ()}   # query + cache sites

This makes scan-over-layers, sharding, checkpointing, and the optimizer's
parameter groups (no weight decay on scales; 50x LR boost on *activation*
scales, paper §3.1) uniform tree operations.

Modes
-----
* ``train``  — fake-quant active (LSQ for static scales, STE everywhere)
* ``calib``  — quantization *observed not applied* at activation sites;
               each site writes its |x|-percentile statistic into a collector
               dict that mirrors the params structure (scan stacks it)
* ``off``    — no quantization (fp16 teacher / baseline)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import calibration as calib
from repro.core.precision import PrecisionPolicy, parse_policy
from repro.core.quantizer import (dynamic_fake_quant, lsq_fake_quant,
                                  pack_int4, quantize_to_int, unpack_int4,
                                  weight_scale_shape)

# Param-dict keys holding quantizer step sizes
SCALE_KEYS = ("s_w", "s_in", "s_q", "s_k", "s_v", "s_state")
ACT_SCALE_KEYS = ("s_in", "s_q", "s_k", "s_v", "s_state")  # 50x LR boost set
# map scale key -> which policy bits apply
_SITE_BITS = {
    "s_in": "act", "s_q": "query", "s_k": "cache", "s_v": "cache",
    "s_state": "cache", "s_w": "weight",
}


@dataclass(frozen=True)
class QuantCtx:
    policy: PrecisionPolicy
    mode: str = "train"                  # train | calib | off
    act_calib_method: str = "quantile"   # quantile | max
    # distribution hints (set by the launch layer; empty = no constraints):
    # attn_shard_mode: "" | "kv_rep" (replicate K/V, shard q heads) |
    #                  "seq" (sequence-parallel attention, replicate K/V)
    attn_shard_mode: str = ""
    batch_axes: tuple = ()
    # Serving weight layout: "bf16" keeps fake-quant einsums on bf16 params;
    # "w4a8" routes every qlinear through the packed-int4 x int8 matmul
    # (requires attach_w4a8_exports on the served tree — strict, no fallback).
    weights_layout: str = "bf16"
    w4a8_backend: str = "auto"           # auto | pallas | ref
    # Serving mesh (None off a mesh): GSPMD cannot partition a Pallas
    # call, so the kernels run per device of it under a shard_map
    mesh: Any = None

    @property
    def off(self) -> bool:
        return self.mode == "off" or not self.policy.enabled

    def bits_for(self, site: str) -> int:
        kind = _SITE_BITS[site]
        p = self.policy
        return {"act": p.act_bits, "query": p.query_bits,
                "cache": p.cache_bits, "weight": p.weight_bits}[kind]

    def with_mode(self, mode: str) -> "QuantCtx":
        return replace(self, mode=mode)


def make_ctx(policy: str | PrecisionPolicy, mode: str = "train",
             act_calib_method: str = "quantile",
             attn_shard_mode: str = "", batch_axes: tuple = (),
             weights_layout: str = "bf16",
             w4a8_backend: str = "auto", mesh=None) -> QuantCtx:
    if isinstance(policy, str):
        policy = parse_policy(policy)
    return QuantCtx(policy=policy, mode=mode,
                    act_calib_method=act_calib_method,
                    attn_shard_mode=attn_shard_mode, batch_axes=batch_axes,
                    weights_layout=weights_layout, w4a8_backend=w4a8_backend,
                    mesh=mesh)


# --------------------------------------------------------------------------
# Site helpers (called from model code)
# --------------------------------------------------------------------------

def _stat(ctx: QuantCtx, x: jnp.ndarray, bits: int) -> jnp.ndarray:
    if ctx.act_calib_method == "max":
        return calib.act_max_stat(x, bits)
    if ctx.act_calib_method == "chan_max":
        # per-channel |x| maxima (SmoothQuant calibration)
        xf = jnp.abs(x.astype(jnp.float32))
        return jnp.max(xf.reshape(-1, x.shape[-1]), axis=0)
    return calib.act_percentile_stat(x, bits)


def quantize_act(ctx: QuantCtx, x: jnp.ndarray, p: Dict[str, Any], site: str,
                 col: Optional[Dict[str, Any]] = None,
                 bits: Optional[int] = None) -> jnp.ndarray:
    """Quantize an activation-class site (``s_in``/``s_q``/``s_k``/``s_v``).

    ``p`` is the owning param dict (provides the learned scale in static
    mode); ``col`` is the calibration collector.
    """
    if ctx.off:
        return x
    bits = bits if bits is not None else ctx.bits_for(site)
    if bits >= 16 and site == "s_in":
        return x  # 16-bit body activations: disabled policy artifact
    if ctx.mode == "calib":
        if col is not None:
            col[site] = _stat(ctx, x, bits)
        return x
    if ctx.policy.act_dynamic:
        return dynamic_fake_quant(x, bits, axis=-1)
    return lsq_fake_quant(x, p[site], bits)


def quantize_weight_p(ctx: QuantCtx, p: Dict[str, Any],
                      bits: Optional[int] = None,
                      key: str = "w") -> jnp.ndarray:
    """Fake-quant a weight from its param dict (LSQ per-output-channel)."""
    w = p[key]
    if ctx.off:
        return w
    bits = bits if bits is not None else ctx.policy.weight_bits
    if bits >= 16:
        return w
    return lsq_fake_quant(w, p["s_w"], bits)


def qlinear(ctx: QuantCtx, x: jnp.ndarray, p: Dict[str, Any],
            col: Optional[Dict[str, Any]] = None,
            act_bits: Optional[int] = None,
            weight_bits: Optional[int] = None,
            out_major: bool = False) -> jnp.ndarray:
    """Quantized linear: fake-quant input + weight, then matmul (+ bias).

    ``act_bits``/``weight_bits`` override the body policy for special sites
    (head: 8/8; router: 8/8). ``out_major`` says ``p["w"]`` is stored
    (d_out, d_in) with ``s_w`` shaped (d_out, 1), as the tied head's
    embedding table is: it is quantized and contracted as stored, so
    neither it nor its gradient is transposed.

    Under ``weights_layout="w4a8"`` the matmul instead consumes the packed
    int4 export attached next to this linear (see ``attach_w4a8_exports``)
    with per-token dynamic int8 activations — real integer arithmetic, not
    fake-quant. Missing exports raise: a silent bf16 fallback would defeat
    the whole point of the layout (weight-HBM streaming).
    """
    if ctx.weights_layout == "w4a8" and ctx.mode != "calib" and not ctx.off:
        exp = p.get("w4a8")
        if exp is None:
            raise ValueError(
                "weights_layout='w4a8' but this linear carries no packed "
                "export; run qat.attach_w4a8_exports(params, policy) on the "
                "served tree (keys present: %s)" % sorted(p.keys()))
        return w4a8_qlinear(ctx, x, exp)
    xq = quantize_act(ctx, x, p, "s_in", col, bits=act_bits)
    wq = quantize_weight_p(ctx, p, bits=weight_bits)
    y = jnp.einsum("...i,oi->...o" if out_major else "...i,io->...o",
                   xq, wq)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def w4a8_use_pallas(ctx: QuantCtx) -> bool:
    """Backend pick for the packed matmul: Pallas on TPU, XLA ref elsewhere
    (``w4a8_backend`` forces either; off-TPU "pallas" runs interpret mode)."""
    if ctx.w4a8_backend == "pallas":
        return True
    if ctx.w4a8_backend == "ref":
        return False
    return jax.default_backend() == "tpu"


def w4a8_qlinear(ctx: QuantCtx, x: jnp.ndarray, exp: Dict[str, Any]) -> jnp.ndarray:
    """Packed-int4-weight x dynamic-int8-activation linear (serve hot path)."""
    from repro.kernels.w4a8.ops import w4a8_linear
    return w4a8_linear(x, exp, out_dtype=x.dtype,
                       use_pallas=w4a8_use_pallas(ctx), mesh=ctx.mesh)


def cache_dtype(ctx: QuantCtx):
    """Storage dtype for cache tensors under this policy."""
    import jax.numpy as jnp
    if ctx.off or ctx.policy.cache_bits >= 16:
        return jnp.bfloat16
    return jnp.int8


def cache_quantize(ctx: QuantCtx, x, axis: int = -1):
    """Quantize a tensor for cache storage; returns (stored, scale).

    C16 / disabled policies store bf16 with unit scales (same cache
    structure either way, so serve code is policy-agnostic)."""
    import jax.numpy as jnp
    from repro.core.quantizer import dynamic_quantize_to_int
    if ctx.off or ctx.policy.cache_bits >= 16:
        s_shape = x.shape[:-1] + (1,) if axis in (-1, x.ndim - 1) else x.shape
        return x.astype(jnp.bfloat16), jnp.ones(s_shape, jnp.float32)
    return dynamic_quantize_to_int(x, ctx.policy.cache_bits, axis=axis)


# --------------------------------------------------------------------------
# Parameter-tree plumbing
# --------------------------------------------------------------------------

def scale_params_for_weight(w: jnp.ndarray) -> jnp.ndarray:
    """Placeholder per-output-channel scale (calibrated before training)."""
    return jnp.ones(weight_scale_shape(w.shape), jnp.float32)


def init_linear(key, d_in: int, d_out: int, bias: bool = False,
                dtype=jnp.bfloat16, scale: Optional[float] = None) -> Dict:
    std = scale if scale is not None else d_in ** -0.5
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * std
    p = {"w": w.astype(dtype), "s_w": scale_params_for_weight(w),
         "s_in": jnp.float32(1.0)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def is_scale_key(k: str) -> bool:
    return isinstance(k, str) and k.startswith("s_") and k in SCALE_KEYS


def scale_mask(params) -> Any:
    """Pytree of bools: True on quantizer-scale leaves (no weight decay)."""
    return _mask_by_key(params, lambda k: is_scale_key(k))


def act_scale_mask(params) -> Any:
    """True only on activation/cache/query scale leaves (50x LR boost)."""
    return _mask_by_key(params, lambda k: k in ACT_SCALE_KEYS)


def _mask_by_key(tree, pred):
    if isinstance(tree, dict):
        return {k: (jax.tree.map(lambda _: pred(k), v)
                    if not isinstance(v, (dict, list, tuple)) else
                    _mask_by_key(v, pred) if not pred(k) else
                    jax.tree.map(lambda _: True, v))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        t = [_mask_by_key(v, pred) for v in tree]
        return type(tree)(t)
    return False


# --------------------------------------------------------------------------
# Calibration passes
# --------------------------------------------------------------------------

def calibrate_weight_scales(params, policy: PrecisionPolicy,
                            method: str = "mse"):
    """Recompute every ``s_w`` from its sibling ``w`` (Eq. 2 by default).

    The head is a special site: quantized at ``head_bits`` (8, not W-bits),
    and when embeddings are tied it has no ``w`` sibling — its scale is
    calibrated from the transposed embedding table."""
    if not policy.enabled:
        return params

    def walk(tree):
        if isinstance(tree, dict):
            out = dict(tree)
            if "w" in tree and "s_w" in tree:
                bits = policy.weight_bits
                out["s_w"] = calib.weight_scale(tree["w"], bits, method=method)
            for k, v in tree.items():
                if isinstance(v, (dict, list, tuple)) and k != "w":
                    out[k] = walk(v)
            return out
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v) for v in tree)
        return tree

    out = walk(params)
    if isinstance(out, dict) and "head" in out and "s_w" in out["head"]:
        head = dict(out["head"])
        w_head = head["w"] if "w" in head else out["embed"]["w"].T
        head["s_w"] = calib.weight_scale(w_head, policy.head_bits,
                                         method=method)
        out["head"] = head
    return out


def merge_act_scales(params, stats_batches, policy: PrecisionPolicy):
    """Average per-batch calibration stats and write activation scales.

    ``stats_batches``: list of collector pytrees (same structure), each leaf a
    percentile landmark of |x|. Scale = landmark / b_u for the site's bits.
    """
    if not stats_batches:
        return params
    mean_stats = jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs), axis=0),
                              *stats_batches)

    def walk(p, s):
        if isinstance(p, dict):
            out = dict(p)
            for k, v in p.items():
                if isinstance(s, dict) and k in s:
                    if k in ACT_SCALE_KEYS:
                        bits = _bits_of(policy, k)
                        out[k] = calib.act_scale_from_stat(
                            s[k].astype(jnp.float32), bits).astype(v.dtype) \
                            if hasattr(v, "dtype") else s[k]
                    elif isinstance(v, (dict, list, tuple)):
                        out[k] = walk(v, s[k])
            return out
        if isinstance(p, (list, tuple)) and isinstance(s, (list, tuple)):
            return type(p)(walk(a, b) for a, b in zip(p, s))
        return p

    return walk(params, mean_stats)


def _bits_of(policy: PrecisionPolicy, key: str) -> int:
    kind = _SITE_BITS[key]
    return {"act": policy.act_bits, "query": policy.query_bits,
            "cache": policy.cache_bits, "weight": policy.weight_bits}[kind]


# --------------------------------------------------------------------------
# Deployment export (real integers for the serving path / kernels)
# --------------------------------------------------------------------------

def export_linear_int(p: Dict[str, Any], weight_bits: int) -> Dict[str, Any]:
    """Convert a fake-quant linear to deployable integers.

    4-bit weights are nibble-packed along d_in pairs (kernel layout);
    8-bit kept as int8. Returns {"wq", "s_w", ["b"], "packed": bool}.
    """
    w, s_w = p["w"], p["s_w"]
    q = quantize_to_int(w, s_w, weight_bits)          # int8 values
    out = {"s_w": s_w.astype(jnp.float32)}
    if "b" in p:
        out["b"] = p["b"]
    if weight_bits <= 4:
        out["wq"] = pack_int4(jnp.swapaxes(q, -1, -2))  # (d_out, d_in/2) packed
        out["packed"] = True
    else:
        out["wq"] = q
        out["packed"] = False
    if "s_in" in p:
        out["s_in"] = p["s_in"].astype(jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames="trained_bits")
def export_linear_w4(p: Dict[str, Any], trained_bits: int = 4) -> Dict[str, Any]:
    """Pack one linear into the serve-path int4 layout.

    Returns ``{"wq": (d_out, d_in/2) uint8, "s_w": f32 per-out-channel,
    ["b"]}`` — exactly what ``kernels.w4a8.ops.w4a8_linear`` consumes. Two
    scale fixups happen here rather than at load time:

    * a site trained at ``trained_bits > 4`` (the 8-bit head) is re-gridded
      onto the int4 lattice: ``s4 = s_trained * (q_max(trained) / 7)``
    * uncalibrated placeholder scales (``init_linear``'s all-ones) would
      quantize real weights to all-zeros, so exactly-1.0 channels fall back
      to per-channel absmax / 7

    No Python-bool leaves (``export_linear_int``'s ``"packed"``): the export
    rides the param pytree through ``jax.jit`` / ``lax.scan``, where a bool
    leaf would become a tracer.

    Jitted so the f32 quantize chain fuses: run op by op, a layer-stacked
    MLP weight of qwen2.5-3b holds three 3.2 GB f32 temporaries at once,
    which with the bf16 tree filled a 16 GB chip.
    """
    from repro.core.quantizer import qbounds
    w = p["w"]
    if w.shape[-2] % 2:
        raise ValueError(f"int4 packing needs even d_in, got {w.shape[-2]}")
    raw = p["s_w"].astype(jnp.float32)
    qp_t = qbounds(trained_bits)[1]
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    s4 = jnp.where(raw == 1.0, jnp.maximum(absmax / 7.0, 1e-9),
                   raw * (qp_t / 7.0))
    q = quantize_to_int(w, s4, 4)
    out = {"wq": pack_int4(jnp.swapaxes(q, -1, -2)), "s_w": s4}
    if "b" in p:
        out["b"] = p["b"]
    return out


def attach_w4a8_exports(params, policy: PrecisionPolicy):
    """Attach a packed ``"w4a8"`` export inside every served linear dict.

    Returns a new tree (input untouched). Walk rules mirror
    :func:`calibrate_weight_scales`:

    * any dict with ``w``/``s_w`` siblings is a linear — body sites pack at
      ``policy.weight_bits``'s lattice (re-gridded to int4)
    * MoE expert banks (``wg``/``wu``/``wd`` next to a ``router``) are
      skipped: ``_expert_linear`` batches over the expert axis with its own
      einsum and has no packed kernel — only the router is exported
    * the head packs at ``policy.head_bits``; when embeddings are tied it has
      no ``w`` and exports from the transposed embedding table

    Scan-stacked segment linears keep their leading ``(rep,)`` axis on
    ``wq``/``s_w``, so exports slice per-layer inside ``lax.scan`` exactly
    like the weights they shadow.
    """
    if not policy.enabled:
        raise ValueError("w4a8 export needs a quantized policy "
                         f"(got {policy.name})")

    def walk(tree):
        if isinstance(tree, dict):
            moe = "router" in tree
            out = {}
            for k, v in tree.items():
                if isinstance(v, dict) and "w" in v and "s_w" in v:
                    if moe and k in ("wg", "wu", "wd", "w1", "w2"):
                        out[k] = v
                        continue
                    nv = dict(v)
                    nv["w4a8"] = export_linear_w4(v, policy.weight_bits)
                    out[k] = nv
                elif isinstance(v, (dict, list, tuple)):
                    out[k] = walk(v)
                else:
                    out[k] = v
            return out
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v) for v in tree)
        return tree

    out = walk(params)
    if isinstance(out, dict) and "head" in out and "s_w" in out["head"]:
        head = dict(out["head"])
        hp = {"w": head["w"] if "w" in head else out["embed"]["w"].T,
              "s_w": head["s_w"]}
        if "b" in head:
            hp["b"] = head["b"]
        head["w4a8"] = export_linear_w4(hp, policy.head_bits)
        out["head"] = head
    return out


def attach_w4a8_ref_planes(params):
    """Cache each export's unpacked ``(d_in, d_out)`` int8 plane as
    ``w4a8["wf"]`` — the XLA-ref backend's decode-time weight form.

    The Pallas kernel unpacks nibbles in-registers per tile, which is free
    on TPU; XLA:CPU cannot fuse the unpack into its BLAS gemm, so without
    this cache the ref serve path re-materializes the unpacked matrix on
    every decode step — measurably slower than the bf16 fake-quant path it
    replaces. Unpacking once at engine construction restores parity. The
    plane is derived purely from ``wq`` (bf16 ``w`` stays unread: the NaN-
    poison lint still binds), costs half the bytes of the bf16 weights it
    shadows, and feeds the exact same integer gemm, so ref results stay
    bit-identical to Pallas. Call only when serving with the ref backend —
    a TPU engine never needs it.
    """

    def walk(tree):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if k == "w4a8" and isinstance(v, dict) and "wq" in v:
                    nv = dict(v)
                    nv["wf"] = jnp.swapaxes(unpack_int4(v["wq"]), -1, -2)
                    out[k] = nv
                elif isinstance(v, (dict, list, tuple)):
                    out[k] = walk(v)
                else:
                    out[k] = v
            return out
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v) for v in tree)
        return tree

    return walk(params)


def w4a8_weight_bytes(params) -> Dict[str, int]:
    """HBM weight-streaming accounting for an export-attached tree.

    ``packed``: bytes the w4a8 serve path reads per full forward (wq + s_w +
    b of every export); ``replaced``: bytes the bf16 layout would have
    streamed for the same matmuls (tied head counts the embedding table —
    the logits matmul reads it every step either way). The ``wf`` ref-
    backend plane is excluded: it is a CPU decode cache, not part of the
    streamed packed layout.
    """
    packed = replaced = 0

    def walk(tree):
        nonlocal packed, replaced
        if isinstance(tree, dict):
            if "w4a8" in tree:
                for key, leaf in tree["w4a8"].items():
                    if key == "wf":
                        continue
                    packed += int(leaf.size) * leaf.dtype.itemsize
                if "w" in tree:
                    replaced += int(tree["w"].size) * tree["w"].dtype.itemsize
                if "b" in tree:
                    replaced += int(tree["b"].size) * tree["b"].dtype.itemsize
            for v in tree.values():
                if isinstance(v, (dict, list, tuple)):
                    walk(v)
        elif isinstance(tree, (list, tuple)):
            for v in tree:
                walk(v)

    walk(params)
    if (isinstance(params, dict) and "head" in params
            and "w4a8" in params.get("head", {})
            and "w" not in params["head"] and "embed" in params):
        w = params["embed"]["w"]
        replaced += int(w.size) * w.dtype.itemsize
    return {"packed": packed, "replaced": replaced}
