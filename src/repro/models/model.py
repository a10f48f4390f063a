"""Unified model stack covering all assigned architectures.

One decoder skeleton parameterized by ``ModelConfig.block_pattern``:
dense/MoE GQA transformers (qwen*, mixtral, moonshot), hybrid RG-LRU +
local-attention (recurrentgemma), hybrid Mamba-2 + NoPE attention with
Granite's multipliers (granite-4.0-h), mLSTM/sLSTM (xlstm), an
encoder-decoder wrapper (whisper), and an M-RoPE VLM backbone (qwen2-vl).

Layers are scanned: the repeating super-block (= block_pattern) is stacked
along a leading ``repeat`` axis and driven by ``lax.scan``, keeping HLO size
depth-independent (critical for the 512-device dry-run compile). Pattern
remainders form a second, repeat-1 segment.

Three entry points per model:
* ``forward``      — training / teacher path (logits [+ calib stats, moe aux])
* ``prefill``      — forward + emit quantized caches for serving
* ``decode_step``  — one token against the quantized cache
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTENTION_BLOCKS, BLOCK_ATTN,
                                BLOCK_LOCAL_ATTN, BLOCK_MAMBA2, BLOCK_MLSTM,
                                BLOCK_RGLRU, BLOCK_SLSTM, ModelConfig)
from repro.core.qat import QuantCtx, init_linear, qlinear
from repro.models import blocks as B
from repro.models import recurrent as R
from repro.models.common import (init_norm, mrope_tables, norm, rope_tables,
                                 subcol)


# --------------------------------------------------------------------------
# Layer plan
# --------------------------------------------------------------------------

def segment_plan(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """[(kinds, repeat), ...] — full-pattern segment + optional remainder."""
    pat = cfg.block_pattern
    n_full, rem = divmod(cfg.n_layers, len(pat))
    plan = []
    if n_full:
        plan.append((pat, n_full))
    if rem:
        plan.append((pat[:rem], 1))
    return plan


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _init_block(cfg: ModelConfig, kind: str, key, *, decoder_cross: bool,
                dtype) -> Dict:
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"ln1": init_norm(cfg.d_model, cfg.norm_type, dtype)}
    if kind in ATTENTION_BLOCKS:
        p["attn"] = B.init_attention(cfg, ks[0], dtype=dtype)
        if decoder_cross:
            p["ln_x"] = init_norm(cfg.d_model, cfg.norm_type, dtype)
            p["xattn"] = B.init_attention(cfg, ks[1], cross=True, dtype=dtype)
        p["ln2"] = init_norm(cfg.d_model, cfg.norm_type, dtype)
        p["moe" if cfg.is_moe else "mlp"] = (
            B.init_moe(cfg, ks[2], dtype) if cfg.is_moe
            else B.init_mlp(cfg, ks[2], dtype))
    elif kind == BLOCK_RGLRU:
        p["rglru"] = R.init_rglru(cfg, ks[0], dtype)
        p["ln2"] = init_norm(cfg.d_model, cfg.norm_type, dtype)
        p["mlp"] = B.init_mlp(cfg, ks[1], dtype)
    elif kind == BLOCK_MAMBA2:
        p["mamba"] = R.init_mamba2(cfg, ks[0], dtype)
        p["ln2"] = init_norm(cfg.d_model, cfg.norm_type, dtype)
        p["mlp"] = B.init_mlp(cfg, ks[1], dtype)
    elif kind == BLOCK_MLSTM:
        p["cell"] = R.init_mlstm(cfg, ks[0], dtype)
    elif kind == BLOCK_SLSTM:
        p["cell"] = R.init_slstm(cfg, ks[0], dtype)
    else:
        raise ValueError(kind)
    return p


def _init_segment(cfg, kinds, repeat, key, *, decoder_cross, dtype):
    def one(k):
        kk = jax.random.split(k, len(kinds))
        return {str(i): _init_block(cfg, kind, kk[i],
                                    decoder_cross=decoder_cross, dtype=dtype)
                for i, kind in enumerate(kinds)}
    layers = [one(k) for k in jax.random.split(key, repeat)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": {"w": (jax.random.normal(ks[0], (cfg.vocab_size,
                                                  cfg.d_model), jnp.float32)
                        * 0.02).astype(dtype)},
        "final_norm": init_norm(cfg.d_model, cfg.norm_type, dtype),
        "segments": [
            _init_segment(cfg, kinds, rep, jax.random.fold_in(ks[1], i),
                          decoder_cross=cfg.is_encdec, dtype=dtype)
            for i, (kinds, rep) in enumerate(segment_plan(cfg))],
    }
    if cfg.tie_embeddings:
        # tied head still owns its quantizer scales (8-bit head site)
        params["head"] = {"s_w": jnp.ones((1, cfg.vocab_size), jnp.float32),
                          "s_in": jnp.float32(1.0)}
    else:
        params["head"] = init_linear(ks[2], cfg.d_model, cfg.vocab_size,
                                     dtype=dtype)
    if cfg.max_position_embeddings:
        params["pos_embed"] = {
            "w": (jax.random.normal(ks[3], (cfg.max_position_embeddings,
                                            cfg.d_model), jnp.float32)
                  * 0.02).astype(dtype)}
    if cfg.is_encdec:
        params["encoder"] = {
            "pos_embed": {"w": (jax.random.normal(
                ks[4], (cfg.encoder_seq, cfg.d_model), jnp.float32)
                * 0.02).astype(dtype)},
            "segments": [_init_segment(cfg, (BLOCK_ATTN,), cfg.encoder_layers,
                                       ks[5], decoder_cross=False,
                                       dtype=dtype)],
            "final_norm": init_norm(cfg.d_model, cfg.norm_type, dtype),
        }
    return params


# --------------------------------------------------------------------------
# Forward (train / teacher / calibration)
# --------------------------------------------------------------------------

def _rope_for(cfg: ModelConfig, batch: Dict, S: int):
    if not cfg.rope_theta:
        return None
    hd = cfg.resolved_head_dim
    if cfg.mrope and "positions" in batch:
        return mrope_tables(batch["positions"], hd, cfg.rope_theta)
    return rope_tables(jnp.arange(S), hd, cfg.rope_theta)


def _residual(cfg: ModelConfig, x: jnp.ndarray, y: jnp.ndarray):
    """x + y, the branch scaled by ``residual_multiplier`` where the
    config sets one (Granite)."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _ffn_tail(cfg, ctx, p, x, col=None):
    """ln2 + MoE/MLP + residual — the post-attention half of an attention
    block, shared by the forward/prefill, decode, and chunked-prefill
    paths. Returns (x, moe_aux)."""
    h = norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    if cfg.is_moe:
        y, aux = B.moe_fwd(cfg, ctx, p["moe"], h, subcol(col, "moe"))
    else:
        y = B.mlp_fwd(cfg, ctx, p["mlp"], h, subcol(col, "mlp"))
        aux = jnp.float32(0.0)
    return _residual(cfg, x, y), aux


def _block_fwd(cfg, ctx, kind, p, x, consts, col, *, prefill=False):
    """Returns (x, aux, cache|None)."""
    aux = jnp.float32(0.0)
    cache = None
    if kind in ATTENTION_BLOCKS:
        window = (cfg.local_window if kind == BLOCK_LOCAL_ATTN
                  else cfg.sliding_window)
        h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
        if prefill:
            a, cache_sa = B.attn_prefill(
                cfg, ctx, p["attn"], h, consts["rope"], subcol(col, "attn"),
                window=window, cache_len=consts.get("cache_len", 0),
                lengths=consts.get("lengths"),
                page_size=consts.get("page_size", 0))
            cache = {"self": cache_sa}
        else:
            a = B.attn_fwd(cfg, ctx, p["attn"], h, consts["rope"],
                           subcol(col, "attn"), window=window)
        x = _residual(cfg, x, a)
        if "xattn" in p:
            h = norm(x, p["ln_x"], cfg.norm_type, cfg.norm_eps)
            if prefill:
                a, cache_xa = B.attn_prefill(
                    cfg, ctx, p["xattn"], h, None, subcol(col, "xattn"),
                    enc_out=consts["enc_out"])
                cache["cross"] = cache_xa
            else:
                a = B.attn_fwd(cfg, ctx, p["xattn"], h, None,
                               subcol(col, "xattn"),
                               enc_out=consts["enc_out"])
            x = _residual(cfg, x, a)
        x, aux = _ffn_tail(cfg, ctx, p, x, col)
    elif kind == BLOCK_RGLRU:
        h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
        if prefill:
            y, cache = R.rglru_prefill(cfg, ctx, p["rglru"], h,
                                       subcol(col, "rglru"))
        else:
            y = R.rglru_fwd(cfg, ctx, p["rglru"], h, subcol(col, "rglru"))
        x = _residual(cfg, x, y)
        h = norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
        x = _residual(cfg, x, B.mlp_fwd(cfg, ctx, p["mlp"], h,
                                        subcol(col, "mlp")))
    elif kind == BLOCK_MAMBA2:
        h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
        with jax.named_scope("mamba"):
            y = R.mamba2_fwd(cfg, ctx, p["mamba"], h, subcol(col, "mamba"),
                             return_cache=prefill)
        if prefill:
            y, cache = y
        x = _residual(cfg, x, y)
        h = norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
        x = _residual(cfg, x, B.mlp_fwd(cfg, ctx, p["mlp"], h,
                                        subcol(col, "mlp")))
    elif kind in (BLOCK_MLSTM, BLOCK_SLSTM):
        h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
        mod = R.mlstm_prefill if kind == BLOCK_MLSTM else R.slstm_prefill
        fwd = R.mlstm_fwd if kind == BLOCK_MLSTM else R.slstm_fwd
        if prefill:
            y, cache = mod(cfg, ctx, p["cell"], h, subcol(col, "cell"))
        else:
            y = fwd(cfg, ctx, p["cell"], h, subcol(col, "cell"))
        x = _residual(cfg, x, y)
    else:
        raise ValueError(kind)
    return x, aux, cache


def _run_stack(cfg, ctx, segments_params, plan, x, consts, *,
               collect: bool, prefill: bool = False, remat: bool = False):
    """Scan every segment. Returns (x, cols, auxs, caches)."""
    cols, auxs, caches = [], [], []
    for seg_p, (kinds, rep) in zip(segments_params, plan):
        def body(xc, layer_p):
            col = {} if collect else None
            aux = jnp.float32(0.0)
            cache = {}
            for i, kind in enumerate(kinds):
                xc, a, c = _block_fwd(cfg, ctx, kind, layer_p[str(i)], xc,
                                      consts, subcol(col, str(i)),
                                      prefill=prefill)
                aux = aux + a
                if prefill:
                    cache[str(i)] = c
            ys = (col if collect else {}, aux, cache if prefill else {})
            return xc, ys
        if remat:
            body = jax.checkpoint(body)  # per-layer activation rematerialization
        x, (col_s, aux_s, cache_s) = jax.lax.scan(body, x, seg_p)
        cols.append(col_s)
        auxs.append(jnp.sum(aux_s))
        caches.append(cache_s)
    return x, cols, auxs, caches


def _embed_tokens(cfg: ModelConfig, params, tokens: jnp.ndarray):
    """Token embeddings, times ``embedding_multiplier`` where the config
    sets one (Granite)."""
    x = jnp.take(params["embed"]["w"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _embed(cfg: ModelConfig, params, batch: Dict) -> jnp.ndarray:
    x = _embed_tokens(cfg, params, batch["tokens"])
    if "patches" in batch:          # VLM: precomputed patch-embedding prefix
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    if "pos_embed" in params:
        S = x.shape[1]
        off = batch.get("pos_offset", 0)
        pos = params["pos_embed"]["w"]
        x = x + jax.lax.dynamic_slice_in_dim(pos, off, S, 0)[None]
    return x


def _encode(cfg, ctx, params, batch, col):
    enc = params["encoder"]
    h = batch["frames"].astype(enc["pos_embed"]["w"].dtype)
    h = h + enc["pos_embed"]["w"][None, :h.shape[1]]
    consts = {"rope": None, "enc_out": None}
    plan = [((BLOCK_ATTN,), cfg.encoder_layers)]
    # encoder attention is bidirectional: causal off via window=0 & flag
    def body(xc, layer_p):
        cc = {} if col is not None else None
        hh = norm(xc, layer_p["0"]["ln1"], cfg.norm_type, cfg.norm_eps)
        a = B.attn_fwd(cfg, ctx, layer_p["0"]["attn"], hh, None,
                       subcol(cc, "0attn"), causal=False)
        xc = xc + a
        hh = norm(xc, layer_p["0"]["ln2"], cfg.norm_type, cfg.norm_eps)
        xc = xc + B.mlp_fwd(cfg, ctx, layer_p["0"]["mlp"], hh,
                            subcol(cc, "0mlp"))
        return xc, (cc if col is not None else {})
    h, enc_cols = jax.lax.scan(body, h, enc["segments"][0])
    if col is not None:
        col["encoder"] = enc_cols
    return norm(h, enc["final_norm"], cfg.norm_type, cfg.norm_eps)


def head_logits(cfg: ModelConfig, params, ctx: QuantCtx, x: jnp.ndarray,
                col: Optional[Dict] = None) -> jnp.ndarray:
    hb = ctx.policy.head_bits
    tied = cfg.tie_embeddings
    if tied:
        # the (V, d) table as stored; its per-row s_w (1, V) viewed as (V, 1)
        p = {"w": params["embed"]["w"],
             "s_w": params["head"]["s_w"].reshape(-1, 1),
             "s_in": params["head"]["s_in"]}
        if "w4a8" in params["head"]:
            # packed export of embed.w.T (attach_w4a8_exports tied-head case)
            p["w4a8"] = params["head"]["w4a8"]
    else:
        p = params["head"]
    logits = qlinear(ctx, x, p, subcol(col, "head"),
                     act_bits=hb, weight_bits=hb, out_major=tied)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def forward(cfg: ModelConfig, params: Dict, ctx: QuantCtx, batch: Dict,
            collect_stats: bool = False, remat: bool = False):
    """Training/teacher forward. Returns (logits, {"moe_aux", "qstats"}).
    Its phases are named scopes: ``embed``, ``layers`` and ``head``."""
    with jax.named_scope("embed"):
        x = _embed(cfg, params, batch)
    S = x.shape[1]
    col: Optional[Dict] = {} if collect_stats else None
    consts = {"rope": _rope_for(cfg, batch, S), "enc_out": None}
    if cfg.is_encdec:
        consts["enc_out"] = _encode(cfg, ctx, params, batch, col)
    with jax.named_scope("layers"):
        x, cols, auxs, _ = _run_stack(cfg, ctx, params["segments"],
                                      segment_plan(cfg), x, consts,
                                      collect=collect_stats, remat=remat)
    with jax.named_scope("head"):
        x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
        logits = head_logits(cfg, params, ctx, x, col)
    aux = {"moe_aux": sum(auxs) if auxs else jnp.float32(0.0)}
    if collect_stats:
        col["segments"] = cols
        aux["qstats"] = col
    return logits, aux


# --------------------------------------------------------------------------
# Prefill / decode (serving)
# --------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params: Dict, ctx: QuantCtx, batch: Dict,
            cache_budget: int = 0, page_size: int = 0):
    """Forward pass that also emits the quantized serving cache.

    ``cache_budget``: total cache capacity (>= prompt length; extra room for
    decode steps). ``batch["lengths"]`` (B,) optionally marks the valid
    prefix of right-padded rows (batched mixed-length admission): logits are
    taken at each row's last *real* token and the cache records true
    lengths/positions. ``page_size`` > 0 emits *block-shaped* attention
    caches (B, nb, Hkv, page_size, D) for the paged serve engine to scatter
    into its global pool (attention-only decoders). Returns
    (logits, cache_pytree).
    """
    lengths = batch.get("lengths")
    if (lengths is not None or page_size) and (
            cfg.is_encdec
            or any(k not in ATTENTION_BLOCKS for k in cfg.block_pattern)):
        # recurrent scans fold right-padding into their state; only causal
        # attention isolates real tokens from pads
        raise ValueError(
            "batch['lengths'] (right-padded prefill) and page_size (paged "
            "cache) require an attention-only decoder; "
            f"{cfg.name!r} has block pattern {cfg.block_pattern}")
    x = _embed(cfg, params, batch)
    S = x.shape[1]
    consts = {"rope": _rope_for(cfg, batch, S), "enc_out": None,
              "cache_len": cache_budget or S, "lengths": lengths,
              "page_size": page_size}
    if cfg.is_encdec:
        consts["enc_out"] = _encode(cfg, ctx, params, batch, None)
    x, _, _, caches = _run_stack(cfg, ctx, params["segments"],
                                 segment_plan(cfg), x, consts,
                                 collect=False, prefill=True)
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    if lengths is None:
        x_last = x[:, -1:]
        position = jnp.full((x.shape[0],), S, jnp.int32)
    else:
        x_last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
        position = lengths.astype(jnp.int32)
    logits = head_logits(cfg, params, ctx, x_last)
    return logits, {"segments": caches, "position": position}


def _block_decode(cfg, ctx, kind, p, x1, cache, positions, block_tbl=None):
    if kind in ATTENTION_BLOCKS:
        window = (cfg.local_window if kind == BLOCK_LOCAL_ATTN
                  else cfg.sliding_window)
        h = norm(x1, p["ln1"], cfg.norm_type, cfg.norm_eps)
        a, new_sa = B.attn_decode(cfg, ctx, p["attn"], h, cache["self"],
                                  positions, window=window,
                                  block_tbl=block_tbl)
        x1 = _residual(cfg, x1, a)
        new_cache = {"self": new_sa}
        if "xattn" in p:
            h = norm(x1, p["ln_x"], cfg.norm_type, cfg.norm_eps)
            a, _ = B.attn_decode(cfg, ctx, p["xattn"], h, cache["cross"],
                                 positions, cross=True)
            x1 = _residual(cfg, x1, a)
            new_cache["cross"] = cache["cross"]
        x1, _ = _ffn_tail(cfg, ctx, p, x1)
        return x1, new_cache
    if kind in (BLOCK_RGLRU, BLOCK_MAMBA2):
        h = norm(x1, p["ln1"], cfg.norm_type, cfg.norm_eps)
        if kind == BLOCK_RGLRU:
            y, new_c = R.rglru_decode(cfg, ctx, p["rglru"], h, cache)
        else:
            with jax.named_scope("mamba"):
                y, new_c = R.mamba2_decode(cfg, ctx, p["mamba"], h, cache)
        x1 = _residual(cfg, x1, y)
        h = norm(x1, p["ln2"], cfg.norm_type, cfg.norm_eps)
        return _residual(cfg, x1, B.mlp_fwd(cfg, ctx, p["mlp"], h)), new_c
    h = norm(x1, p["ln1"], cfg.norm_type, cfg.norm_eps)
    dec = R.mlstm_decode if kind == BLOCK_MLSTM else R.slstm_decode
    y, new_c = dec(cfg, ctx, p["cell"], h, cache)
    return _residual(cfg, x1, y), new_c


def decode_step(cfg: ModelConfig, params: Dict, ctx: QuantCtx,
                tokens1: jnp.ndarray, cache: Dict):
    """One decode step. tokens1 (B, 1) -> (logits (B, 1, V), new cache).

    A ``block_tbl`` key in the cache switches attention layers to the paged
    layout: commits and reads route through the per-slot block table into
    the global pool (see ``init_cache`` with ``num_blocks``).
    """
    positions = cache["position"]
    block_tbl = cache.get("block_tbl")
    x = _embed_tokens(cfg, params, tokens1)
    if "pos_embed" in params:
        x = x + jnp.take(params["pos_embed"]["w"],
                         jnp.minimum(positions,
                                     params["pos_embed"]["w"].shape[0] - 1),
                         axis=0)[:, None]
    new_caches = []
    for seg_p, seg_c, (kinds, rep) in zip(params["segments"],
                                          cache["segments"],
                                          segment_plan(cfg)):
        def body(xc, inp):
            layer_p, layer_c = inp
            new_lc = {}
            for i, kind in enumerate(kinds):
                xc, nc = _block_decode(cfg, ctx, kind, layer_p[str(i)], xc,
                                       layer_c[str(i)], positions,
                                       block_tbl)
                new_lc[str(i)] = nc
            return xc, new_lc
        x, new_c = jax.lax.scan(body, x, (seg_p, seg_c))
        new_caches.append(new_c)
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = head_logits(cfg, params, ctx, x)
    new_cache = {"segments": new_caches, "position": positions + 1}
    if block_tbl is not None:
        new_cache["block_tbl"] = block_tbl
    return logits, new_cache


def _tail_prologue(cfg: ModelConfig, params: Dict, tokens: jnp.ndarray,
                   cache: Dict, slot: jnp.ndarray, offset: jnp.ndarray,
                   hist_blocks: int, caller: str):
    """Shared entry of the batched-window paths (``prefill_tail`` and the
    speculative ``spec_verify``): embed one window per row at per-row
    absolute offsets, build per-position RoPE tables, and pull each
    row's (optionally ``hist_blocks``-truncated) block table."""
    if "block_tbl" not in cache:
        raise ValueError(f"{caller} requires a paged cache "
                         "(init_cache(..., num_blocks=...))")
    C = tokens.shape[1]
    positions = offset[:, None] + jnp.arange(C)[None]       # (n, C)
    x = _embed_tokens(cfg, params, tokens)                  # (n, C, d)
    if "pos_embed" in params:
        pe = params["pos_embed"]["w"]
        x = x + jnp.take(pe, jnp.minimum(positions, pe.shape[0] - 1),
                         axis=0)
    rope = None
    if cfg.rope_theta:
        rope = rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
    tbl = cache["block_tbl"][slot]                          # (n, T)
    if hist_blocks:
        tbl = tbl[:, :hist_blocks]
    return x, rope, tbl


def _tail_stack(cfg: ModelConfig, params: Dict, ctx: QuantCtx,
                x: jnp.ndarray, rope, cache: Dict, tbl: jnp.ndarray,
                slot: jnp.ndarray, offset: jnp.ndarray,
                chunk_len: jnp.ndarray, attn_fn):
    """Scan the decoder stack over one batched window, committing every
    layer's K/V through the block table. ``attn_fn`` is the per-layer
    attention: ``blocks.attn_chunk_prefill`` for tail/chunked prefill
    (exact bf16 window K/V) or ``blocks.attn_spec_verify`` for the
    speculative verify-wave (decode-exact quantized reads) — both share
    this loop so the batched-window contract can't diverge between the
    two paths. Returns (final-norm'd x, new cache segments)."""
    new_segments = []
    for seg_p, seg_c, (kinds, rep) in zip(params["segments"],
                                          cache["segments"],
                                          segment_plan(cfg)):
        def body(xc, inp):
            layer_p, layer_c = inp
            new_lc = {}
            for i, kind in enumerate(kinds):
                p = layer_p[str(i)]
                h = norm(xc, p["ln1"], cfg.norm_type, cfg.norm_eps)
                a, new_sa = attn_fn(cfg, ctx, p["attn"], h, rope,
                                    layer_c[str(i)]["self"], tbl, slot,
                                    offset, chunk_len)
                xc = _residual(cfg, xc, a)
                xc, _ = _ffn_tail(cfg, ctx, p, xc)
                new_lc[str(i)] = {"self": new_sa}
            return xc, new_lc
        x, new_c = jax.lax.scan(body, x, (seg_p, seg_c))
        new_segments.append(new_c)
    return norm(x, params["final_norm"], cfg.norm_type,
                cfg.norm_eps), new_segments


def prefill_tail(cfg: ModelConfig, params: Dict, ctx: QuantCtx,
                 tokens: jnp.ndarray, cache: Dict, slot: jnp.ndarray,
                 start: jnp.ndarray, n_tokens: jnp.ndarray,
                 hist_blocks: int = 0):
    """Partial prefill from per-row token offsets for a batch of slots.

    The entry point behind both *prefix-shared admission* (the first
    ``start[i]`` tokens were found in the prefix cache and their pool
    blocks are already mapped into ``cache["block_tbl"][slot[i]]`` — only
    the uncached tail is computed) and *chunked prefill* (one fixed-size
    window of a long prompt per call). ``tokens`` (n, C) int32 holds one
    window per row, row i's first token sitting at absolute position
    ``start[i]``; only the first ``n_tokens[i]`` are real (windows are
    right-padded so every call compiles to the same program). Rows with
    ``slot`` at the out-of-range sentinel and ``n_tokens == 0`` are
    padding — the engine buckets the wave width to a power of two — and
    commit nothing.

    Each row's queries attend over the ``start[i]`` tokens already
    resident in the pool — gathered through the row's table and
    dequantized at read, exactly what decode reads
    (``blocks.attn_chunk_prefill``) — plus the window itself (causal,
    exact bf16). The window's K/V are quantized and committed through the
    table at per-row write offsets; the engine must have grown each table
    to cover ``start + n_tokens`` tokens and resolved copy-on-write for
    any shared block in that write range *before* calling. Rows are
    independent, so a batched tail-wave produces exactly the tokens the
    serialized single-slot path would.

    ``hist_blocks`` (trace-time constant > 0) truncates the table walk to
    each row's first ``hist_blocks`` entries so the history gather scales
    with the longest co-batched prompt, not ``max_seq_len`` — it must
    cover every row's ``start + n_tokens`` tokens (the engine buckets it
    to a power of two to bound compile variants). Requires the paged
    attention-only cache (see ``init_cache`` with ``num_blocks``).

    Returns (logits (n, V) at each row's last real token, new cache) —
    meaningful for rows on the final window of their prompt (they feed
    the first sampled token).
    """
    offset, chunk_len = start, n_tokens
    x, rope, tbl = _tail_prologue(cfg, params, tokens, cache, slot, offset,
                                  hist_blocks, caller="prefill_tail")
    x, new_segments = _tail_stack(cfg, params, ctx, x, rope, cache, tbl,
                                  slot, offset, chunk_len,
                                  B.attn_chunk_prefill)
    x_last = jnp.take_along_axis(
        x, jnp.maximum(chunk_len - 1, 0)[:, None, None], axis=1)
    logits = head_logits(cfg, params, ctx, x_last)[:, 0]
    return logits, {
        "segments": new_segments,
        "position": cache["position"].at[slot].set(offset + chunk_len,
                                                   mode="drop"),
        "block_tbl": cache["block_tbl"]}


def spec_verify(cfg: ModelConfig, params: Dict, ctx: QuantCtx,
                tokens: jnp.ndarray, cache: Dict, slot: jnp.ndarray,
                start: jnp.ndarray, n_tokens: jnp.ndarray,
                hist_blocks: int = 0):
    """Speculative-decode verify pass: target logits at EVERY window
    position of a batch of slots, in one compiled call.

    Same per-row ``(start, n_tokens)`` batched-window contract as
    :func:`prefill_tail` — ``tokens`` (n, C) holds row i's window
    ``[last_committed_token, draft_1..draft_k]`` starting at absolute
    position ``start[i]``, padded rows carry ``n_tokens == 0`` and the
    slot sentinel — but where a chunked prefill attends with exact bf16
    window K/V, the verify pass commits the window's *quantized* K/V to
    the pool first and reads them back dequantized
    (``blocks.attn_spec_verify``), reproducing sequential decode-step
    numerics bit-for-bit: logits at window position j equal what
    ``decode_step`` would produce after consuming the window prefix
    through j. The caller samples/accepts against these logits and rolls
    the committed suffix back (device counters + allocator ``trim``) for
    the rejected positions.

    ``hist_blocks`` bounds the per-row table walk like in
    ``prefill_tail`` (must cover every row's ``start + n_tokens``).
    Returns (logits (n, C, V), new cache) — the cache's ``length`` /
    ``position`` are advanced to the full window extent; the engine
    re-clamps them to the accepted extent after acceptance.
    """
    offset, chunk_len = start, n_tokens
    x, rope, tbl = _tail_prologue(cfg, params, tokens, cache, slot, offset,
                                  hist_blocks, caller="spec_verify")
    x, new_segments = _tail_stack(cfg, params, ctx, x, rope, cache, tbl,
                                  slot, offset, chunk_len,
                                  B.attn_spec_verify)
    logits = head_logits(cfg, params, ctx, x)
    return logits, {
        "segments": new_segments,
        "position": cache["position"].at[slot].set(offset + chunk_len,
                                                   mode="drop"),
        "block_tbl": cache["block_tbl"]}


# --------------------------------------------------------------------------
# Cache allocation (for dry-run ShapeDtypeStructs and the serve engine)
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, ctx: QuantCtx, batch_size: int,
               cache_len: int, *, num_blocks: int = 0, page_size: int = 0,
               table_len: int = 0) -> Dict:
    """Blank serving cache with total capacity ``cache_len``.

    ``num_blocks`` > 0 switches attention layers to the *paged* layout: one
    global pool of ``num_blocks`` x ``page_size``-token quantized blocks per
    layer plus a top-level ``block_tbl`` (batch_size, table_len) int32
    mapping each slot's logical block i to a pool block (initialized to the
    ``num_blocks`` sentinel = unallocated). Requires an attention-only,
    non-windowed decoder — the host block allocator owns table contents.
    """
    from repro.core.qat import cache_dtype
    qdt = cache_dtype(ctx)
    if num_blocks and (cfg.is_encdec or cfg.sliding_window or any(
            k != BLOCK_ATTN for k in cfg.block_pattern)):
        raise ValueError(
            "paged KV cache requires a full-attention decoder (no sliding "
            f"window, no recurrence, no cross-attention); {cfg.name!r} has "
            f"block pattern {cfg.block_pattern}")

    def block_cache(kind):
        if kind in ATTENTION_BLOCKS:
            if num_blocks:
                return {"self": B.init_paged_attn_cache(
                    cfg, batch_size, num_blocks, page_size, dtype=qdt)}
            window = (cfg.local_window if kind == BLOCK_LOCAL_ATTN
                      else cfg.sliding_window)
            c = {"self": B.init_attn_cache(cfg, batch_size, cache_len,
                                           window=window, dtype=qdt)}
            if cfg.is_encdec:
                c["cross"] = B.init_attn_cache(cfg, batch_size,
                                               cfg.encoder_seq, dtype=qdt)
            return c
        if kind == BLOCK_RGLRU:
            return R.init_rglru_cache(cfg, batch_size, dtype=qdt)
        if kind == BLOCK_MLSTM:
            return R.init_mlstm_cache(cfg, batch_size, dtype=qdt)
        if kind == BLOCK_MAMBA2:
            return R.init_mamba2_cache(cfg, batch_size, dtype=qdt)
        return R.init_slstm_cache(cfg, batch_size, dtype=qdt)

    segments = []
    for kinds, rep in segment_plan(cfg):
        layer = {str(i): block_cache(kind) for i, kind in enumerate(kinds)}
        segments.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (rep,) + x.shape), layer))
    cache = {"segments": segments,
             "position": jnp.zeros((batch_size,), jnp.int32)}
    if num_blocks:
        cache["block_tbl"] = jnp.full(
            (batch_size, table_len or num_blocks), num_blocks, jnp.int32)
    return cache
