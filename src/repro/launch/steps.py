"""Step functions: QAT train (teacher fwd + student fwd/bwd + AdamW + LSQ),
prefill, and single-token decode. These are the functions the dry-run
lowers and the examples execute.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.distill import silq_loss
from repro.core.precision import parse_policy
from repro.core.qat import make_ctx
from repro.models import decode_step as model_decode
from repro.models import forward, prefill
from repro.optim import adamw_update, cosine_schedule

MOE_AUX_COEF = 0.01


def _text_logits(cfg: ModelConfig, logits: jnp.ndarray) -> jnp.ndarray:
    """Drop the vision-prefix positions for loss computation (VLM)."""
    if cfg.family == "vlm" and cfg.vision_tokens:
        return logits[:, cfg.vision_tokens:]
    return logits


def _row_major(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` in the row-major layout the step's arguments come in."""
    return with_layout_constraint(x, Layout(tuple(range(x.ndim))))


def attn_shard_mode_for(cfg: ModelConfig, model_axis: int) -> str:
    """Pick the attention sharding strategy for this arch on this mesh.

    kv-heads divide the TP axis -> plain head sharding is collective-free.
    Else q-heads divide -> replicate K/V, shard q heads ("kv_rep").
    Else -> sequence-parallel attention ("seq").
    """
    if model_axis <= 1 or cfg.n_kv_heads % model_axis == 0:
        return ""
    if cfg.n_heads % model_axis == 0:
        return "kv_rep"
    return "seq"


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    attn_shard_mode: str = "",
                    batch_axes: tuple = ()) -> Callable:
    """QAT train step, paper-faithful: teacher forward (unquantized, no
    grad), student forward with fake-quant, pure-KD loss (default), AdamW
    with LSQ scale updates (50x LR on activation scales).

    Its phases are ``jax.named_scope``s (``kd_loss``, ``optimizer``;
    ``forward`` adds ``embed``, ``layers`` and ``head``), which reach
    every HLO instruction's ``op_name``, the backward pass's included, and
    so name device time in a profiler trace. They change metadata only,
    not the compiled program."""
    policy = parse_policy(tcfg.precision)
    ctx = make_ctx(policy, act_calib_method=tcfg.act_calib_method,
                   attn_shard_mode=attn_shard_mode, batch_axes=batch_axes)
    tctx = make_ctx("A16-C16-W16", mode="off",
                    attn_shard_mode=attn_shard_mode, batch_axes=batch_axes)
    base_lr = tcfg.scaled_lr()
    remat = tcfg.remat != "none"

    def train_step(params, teacher_params, opt_state, batch, step):
        t_logits, _ = forward(cfg, teacher_params, tctx, batch)
        t_logits = jax.lax.stop_gradient(_text_logits(cfg, t_logits))

        def loss_fn(p):
            logits, aux = forward(cfg, p, ctx, batch, remat=remat)
            with jax.named_scope("kd_loss"):
                loss = silq_loss(_text_logits(cfg, logits), t_logits,
                                 batch["labels"], kd_ratio=tcfg.kd_ratio,
                                 kd_temperature=tcfg.kd_temperature,
                                 mask=batch.get("loss_mask"))
                if cfg.is_moe:
                    loss = loss + MOE_AUX_COEF * aux["moe_aux"]
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        if cfg.tie_embeddings:
            # Left free, TPU layout assignment lays the tied table's
            # gradient (head matmul plus embedding scatter-add)
            # column-major, runs its AdamW update that way, and so re-lays
            # the table and both its f32 moments into and out of every
            # step. Other leaves are left free: pinned, a sharded step
            # gains copies of its sharded weights and moments.
            embed = dict(grads["embed"], w=_row_major(grads["embed"]["w"]))
            grads = dict(grads, embed=embed)
        with jax.named_scope("optimizer"):
            if tcfg.grad_clip:
                from repro.optim.adamw import clip_by_global_norm
                grads, _ = clip_by_global_norm(grads, tcfg.grad_clip)
            lr = cosine_schedule(step, base_lr=base_lr,
                                 total_steps=tcfg.total_steps,
                                 warmup_steps=tcfg.warmup_steps,
                                 min_lr_ratio=tcfg.min_lr_ratio)
            new_params, new_opt = adamw_update(
                params, grads, opt_state, lr=lr, beta1=tcfg.beta1,
                beta2=tcfg.beta2, eps=tcfg.eps,
                weight_decay=tcfg.weight_decay,
                act_scale_lr_mult=tcfg.act_scale_lr_mult)
        return new_params, new_opt, {"loss": loss, "lr": lr}

    return train_step


def make_eval_loss(cfg: ModelConfig, precision: str) -> Callable:
    """Next-token loss of the (fake-)quantized model — benchmark metric."""
    ctx = make_ctx(precision if precision else "A16-C16-W16",
                   mode="train" if precision != "A16-C16-W16" else "off")

    def eval_loss(params, batch):
        from repro.core.distill import next_token_loss
        logits, _ = forward(cfg, params, ctx, batch)
        return next_token_loss(_text_logits(cfg, logits), batch["labels"],
                               batch.get("loss_mask"))

    return eval_loss


def make_prefill_step(cfg: ModelConfig, policy: str,
                      cache_budget: int = 0, attn_shard_mode: str = "",
                      batch_axes: tuple = ()) -> Callable:
    ctx = make_ctx(policy, attn_shard_mode=attn_shard_mode,
                   batch_axes=batch_axes)

    def prefill_step(params, batch):
        return prefill(cfg, params, ctx, batch, cache_budget=cache_budget)

    return prefill_step


def make_serve_step(cfg: ModelConfig, policy: str, attn_shard_mode: str = "",
                    batch_axes: tuple = ()) -> Callable:
    """One decode token for every sequence in the batch (greedy head)."""
    ctx = make_ctx(policy, attn_shard_mode=attn_shard_mode,
                   batch_axes=batch_axes)

    def serve_step(params, tokens1, cache):
        logits, new_cache = model_decode(cfg, params, ctx, tokens1, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return logits, next_tok[:, None], new_cache

    return serve_step
