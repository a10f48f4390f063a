"""End-to-end QAT training driver (SiLQ §3.1 flow).

Flow: (1) obtain/pretrain the fp16 teacher, (2) clone it as the student,
(3) calibrate weight step sizes (convex-MSE, Eq. 2) and — for static
activation policies — activation step sizes (percentile over 5 batches),
(4) train end-to-end with pure-KD loss, LSQ scale learning (50x LR on
activation scales), cosine LR, AdamW, (5) checkpoint/restore with heartbeats
(fault tolerance is exercised by --simulate-failure).

CPU-runnable with --reduced; the full configs drive the same code path on
real hardware.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config, get_reduced_config
from repro.configs.base import TrainConfig
from repro.core.distill import next_token_loss
from repro.core.precision import parse_policy
from repro.core.qat import calibrate_weight_scales, make_ctx, merge_act_scales
from repro.data import MixtureIterator, SyntheticConfig, calibration_batches
from repro.launch.cache import enable_compilation_cache
from repro.launch.steps import make_train_step, _text_logits
from repro.models import forward, init_params
from repro.optim import adamw_init, adamw_update, cosine_schedule
from repro.optim.adamw import clip_by_global_norm
from repro.runtime.fault import HeartbeatFile


def make_teacher_pretrain_step(cfg, lr: float = 1e-3):
    ctx = make_ctx("A16-C16-W16", mode="off")

    def step_fn(params, opt_state, batch):
        def loss_fn(p):
            logits, _ = forward(cfg, p, ctx, batch)
            return next_token_loss(_text_logits(cfg, logits),
                                   batch["labels"], batch.get("loss_mask"))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, _ = clip_by_global_norm(grads, 1.0)
        params, opt_state = adamw_update(params, grads, opt_state, lr=lr,
                                         weight_decay=0.0)
        return params, opt_state, loss

    return jax.jit(step_fn)


def pretrain_teacher(cfg, data_cfg: SyntheticConfig, steps: int, key):
    """Give the synthetic-data teacher something to teach."""
    params = init_params(cfg, key)
    opt = adamw_init(params)
    step_fn = make_teacher_pretrain_step(cfg)
    it = MixtureIterator(data_cfg)
    loss = float("nan")
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        params, opt, loss = step_fn(params, opt, batch)
        if i % 50 == 0:
            print(f"  teacher step {i}: ntp-loss {float(loss):.4f}",
                  flush=True)
    print(f"  teacher final ntp-loss {float(loss):.4f}", flush=True)
    return params


def calibrate(cfg, params, tcfg: TrainConfig, data_cfg: SyntheticConfig):
    """Paper §3.1: weight scales via convex-MSE; activation scales via
    percentile over calibration batches (static policies only)."""
    policy = parse_policy(tcfg.precision)
    params = calibrate_weight_scales(params, policy, tcfg.wgt_calib_method)
    if policy.enabled and policy.acts_static:
        ctx = make_ctx(policy, mode="calib",
                       act_calib_method=tcfg.act_calib_method)
        stats = []
        fwd = jax.jit(lambda p, b: forward(cfg, p, ctx, b,
                                           collect_stats=True)[1]["qstats"])
        for batch in calibration_batches(data_cfg, tcfg.calib_batches):
            stats.append(fwd(params, {"tokens": jnp.asarray(batch["tokens"])}))
        params = merge_act_scales(params, stats, policy)
    return params


def run_qat(arch: str, tcfg: TrainConfig, *, reduced: bool = True,
            teacher_steps: int = 200, ckpt_dir: str | None = None,
            resume: bool = False, log_every: int = 20,
            heartbeat_dir: str | None = None, worker: int = 0,
            simulate_failure_at: int = -1, eval_every: int = 0,
            eval_fn=None):
    cfg = get_reduced_config(arch) if reduced else get_config(arch)
    key = jax.random.PRNGKey(tcfg.seed)
    data_cfg = SyntheticConfig(vocab_size=cfg.vocab_size,
                               seq_len=tcfg.seq_len,
                               batch_size=tcfg.batch_size,
                               dclm_ratio=tcfg.dclm_ratio, seed=tcfg.seed)

    print(f"[qat] teacher pretrain ({teacher_steps} steps)", flush=True)
    teacher = pretrain_teacher(cfg, data_cfg, teacher_steps, key)
    student = jax.tree.map(jnp.copy, teacher)
    print("[qat] calibrating step sizes", flush=True)
    student = calibrate(cfg, student, tcfg, data_cfg)
    opt = adamw_init(student)
    it = MixtureIterator(data_cfg, start_step=1)
    start_step = 0

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    if ckpt and resume and ckpt.latest_step() is not None:
        (student, opt), extra = ckpt.restore((student, opt))
        it.load_state_dict(extra["data"])
        start_step = extra["step"]
        print(f"[qat] resumed from step {start_step}", flush=True)

    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 2))
    hb = HeartbeatFile(heartbeat_dir, worker) if heartbeat_dir else None
    history = []
    for step in range(start_step, tcfg.total_steps):
        t0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        student, opt, metrics = step_fn(student, teacher, opt, batch,
                                        jnp.int32(step))
        dt = time.perf_counter() - t0
        if hb:
            hb.write(step, dt)
        if step == simulate_failure_at:
            print(f"[qat] SIMULATED FAILURE at step {step}", flush=True)
            raise SystemExit(42)
        if step % log_every == 0 or step == tcfg.total_steps - 1:
            print(f"  step {step}: kd-loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} ({dt:.2f}s)", flush=True)
        if eval_every and eval_fn and (step + 1) % eval_every == 0:
            history.append((step + 1, eval_fn(student)))
        if ckpt and (step + 1) % 100 == 0:
            ckpt.save_async(step + 1, (student, opt),
                            {"step": step + 1, "data": it.state_dict()})
    if ckpt:
        ckpt.wait()
    return teacher, student, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--precision", default="A8d-C8-W4")
    ap.add_argument("--teacher-steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full-size config (needs real hardware)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-failure-at", type=int, default=-1)
    args = ap.parse_args()
    enable_compilation_cache()
    tcfg = TrainConfig(precision=args.precision, total_steps=args.steps,
                       ref_steps=args.steps, batch_size=args.batch_size,
                       seq_len=args.seq_len)
    run_qat(args.arch, tcfg, reduced=not args.full,
            teacher_steps=args.teacher_steps, ckpt_dir=args.ckpt_dir,
            resume=args.resume,
            simulate_failure_at=args.simulate_failure_at)


if __name__ == "__main__":
    main()
