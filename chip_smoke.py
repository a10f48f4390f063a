"""Smoke test of the main path on a TPU: quantized serving and the QAT step.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py          # one chip: kernel parity, serve, QAT
    python chip_smoke.py --tp4    # four chips: tp=4 vs tp=1 token streams

One process, phases in order; any failed check exits non-zero:

1. device check: a TPU must be present (no CPU fallback);
2. kernel parity: every Pallas kernel of the serve path at qwen2.5-3b
   widths against its ``ref.py`` oracle, at the tolerance the interpret-mode
   tests use (bitwise where they are bitwise);
3. serve: qwen2.5-3b, all 36 layers, random weights from a seed, served
   A8d-C8-W4 with packed w4a8 weights through the paged, prefix-shared,
   speculative engine; 8 requests over 4 slots sharing a 128-token prefix;
4. QAT: qwen2.5-3b widths cut to 4 layers, weight calibration then a few
   A8d-C8-W4 knowledge-distillation train steps; losses must be finite.

``--tp4`` runs only a tp=4 engine against a tp=1 engine on the same greedy
requests and requires identical token streams.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.core.precision import parse_policy  # noqa: E402
from repro.core.qat import calibrate_weight_scales  # noqa: E402
from repro.core.quantizer import pack_int4  # noqa: E402
from repro.data import MixtureIterator, SyntheticConfig  # noqa: E402
from repro.kernels.kvq_attn import ops as kvq  # noqa: E402
from repro.kernels.kvq_attn import ref as kvq_ref  # noqa: E402
from repro.kernels.w4a8.ops import w4a8_matmul  # noqa: E402
from repro.launch.cache import enable_compilation_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.launch.train import calibrate  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.optim import adamw_init  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.serve.spec import SpecConfig  # noqa: E402

ARCH = "qwen2.5-3b"
POLICY = "A8d-C8-W4"


class SmokeFailure(Exception):
    """A phase's check failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info(need: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0].platform is "
                           f"{d.platform!r}")
    if len(devs) < need:
        raise SmokeFailure(f"needs {need} TPU devices, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak_bytes():
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


# --------------------------------------------------------------------------
# Kernel parity
# --------------------------------------------------------------------------

def _compare(name, got, want, *, atol=0.0, rtol=0.0, bf16_ulp=False):
    """Fail unless ``got`` matches ``want`` elementwise within
    ``atol + rtol * |want|`` (zero: bitwise), or within one bf16 ulp."""
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    require(got.shape == want.shape,
            f"{name}: shape {got.shape} != {want.shape}")
    diff = np.abs(got - want)
    if bf16_ulp:
        bound = 2.0 ** -7 * np.maximum(np.maximum(np.abs(got), np.abs(want)),
                                       2.0 ** -126)
        rule = "<= 1 bf16 ulp"
    else:
        bound = atol + rtol * np.abs(want)
        rule = (f"atol {atol:g} rtol {rtol:g}" if atol or rtol
                else "bitwise")
    err = float(diff.max()) if diff.size else 0.0
    log(f"  {name}: shape {got.shape}, max abs err {err:.3e} ({rule})")
    require(bool(np.all(np.isfinite(got))) and bool(np.all(diff <= bound)),
            f"kernel parity: {name} exceeds {rule}")


def kernel_parity(cfg, *, slots=4, block_size=64, num_blocks=64,
                  table_len=5, window=5, dense_len=1024, seed=0):
    """Each serve-path kernel at ``cfg``'s widths against its oracle."""
    rng = np.random.default_rng(seed)
    d, dff, hd = cfg.d_model, cfg.d_ff, cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    qkv = cfg.q_dim + 2 * Hkv * hd
    kernel = jax.jit(w4a8_matmul, static_argnames=("use_pallas",))
    for (M, K, N, bias) in [(8, d, dff, False), (8, dff, d, False),
                            (8, d, cfg.vocab_size, False), (8, d, qkv, True)]:
        x_q = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
        wp = pack_int4(jnp.asarray(rng.integers(-8, 8, (N, K)), jnp.int8))
        s_x = jnp.asarray(rng.random((M, 1)) * 0.1 + 1e-3, jnp.float32)
        s_w = jnp.asarray(rng.random((N,)) * 0.1 + 1e-3, jnp.float32)
        b = (jnp.asarray(rng.standard_normal(N), jnp.float32) if bias
             else None)
        _compare(f"w4a8 matmul {M}x{K}x{N}{' +bias' if bias else ''}",
                 kernel(x_q, wp, s_x, s_w, b, use_pallas=True),
                 kernel(x_q, wp, s_x, s_w, b, use_pallas=False),
                 bf16_ulp=bias)

    def int_kv(shape):
        """Random int8 K/V payloads of ``shape`` and their f32 scales,
        drawn as in the dense kernel's interpret-mode test (dequantized
        values of order one, as a cache holds)."""
        k, v = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                for _ in range(2))
        s_k, s_v = (jnp.asarray(np.abs(rng.standard_normal(shape[:-1]))
                                * 0.01 + 1e-3, jnp.float32)
                    for _ in range(2))
        return k, v, s_k, s_v

    kp, vp, sk, sv = int_kv((num_blocks, Hkv, block_size, hd))
    tbl = jnp.asarray(rng.permutation(num_blocks)[:slots * table_len]
                      .reshape(slots, table_len), jnp.int32)
    tbl = tbl.at[-1, -2:].set(num_blocks)            # sentinel tail
    full = table_len * block_size
    lengths = jnp.asarray([full, full - 7, block_size + 3, 2 * block_size - 9],
                          jnp.int32)[:slots]
    q = jnp.asarray(rng.standard_normal((slots, H, hd)), jnp.float32)
    # f32 reference: XLA's default TPU matmul precision is one bf16 pass
    with jax.default_matmul_precision("highest"):
        want = jax.jit(kvq_ref.kvq_paged_decode_attn_ref)(
            q, kp, vp, sk, sv, tbl, lengths)
    _compare("paged decode attention",
             jax.jit(kvq.kvq_paged_decode_attn)(q, kp, vp, sk, sv, tbl,
                                                lengths),
             want, atol=2e-5, rtol=2e-5)

    qw = jnp.asarray(rng.standard_normal((slots, window, H, hd)),
                     jnp.float32)
    lens_w = jnp.minimum(lengths[:, None] - window + 1
                         + jnp.arange(window)[None], full)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(kvq_ref.kvq_spec_verify_attn_ref)(
            qw, kp, vp, sk, sv, tbl, lens_w)
    _compare("spec-verify attention",
             jax.jit(kvq.kvq_spec_verify_attn)(qw, kp, vp, sk, sv, tbl,
                                               lens_w),
             want, atol=2e-5, rtol=2e-5)

    tbl_c = jnp.minimum(tbl, num_blocks - 1)
    want = (kvq_ref.gather_paged_kv(kp, tbl_c).astype(jnp.float32)
            * kvq_ref.gather_paged_kv(sk, tbl_c)[..., None])
    gather = jax.jit(kvq.gather_dequant_paged_kv,
                     static_argnames=("use_pallas",))
    _compare("gather-dequant", gather(kp, sk, tbl, use_pallas=True), want)

    src = jnp.asarray([3, 5, 7, 0], jnp.int32)
    dst = jnp.asarray([10, 11, 12, num_blocks], jnp.int32)   # last: padding
    copy = jax.jit(kvq.copy_pool_blocks, static_argnames=("use_pallas",))
    stacked = int_kv((cfg.n_layers, num_blocks, Hkv, block_size, hd))
    for name, leaf in (("int8 payload", stacked[0]),
                       ("f32 scales", stacked[2])):
        want = kvq_ref.copy_pool_blocks_ref(leaf, src, dst)
        _compare(f"pool block copy, {name}",
                 copy(leaf, src, dst, use_pallas=True), want)

    kd, vd, skd, svd = int_kv((slots, Hkv, dense_len, hd))
    q1 = jnp.asarray(rng.standard_normal((slots, H, hd)), jnp.float32)
    len_d = jnp.asarray(rng.integers(1, dense_len + 1, slots), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(kvq_ref.kvq_decode_attn_ref)(q1, kd, vd, skd, svd,
                                                   len_d)
    _compare("dense decode attention",
             jax.jit(kvq.kvq_decode_attn)(q1, kd, vd, skd, svd, len_d),
             want, atol=2e-5, rtol=2e-4)


# --------------------------------------------------------------------------
# Serving
# --------------------------------------------------------------------------

def shared_prefix_requests(vocab: int, *, prefix=128, lengths=(96, 64, 160,
                           192, 224, 256, 144, 200), max_new=32, seed=0):
    """Prompts of 64-256 tokens that share the first ``prefix`` tokens
    (shorter ones are a prefix of it). Served after the first, the others
    hit its cached blocks, finish their prompts in tail waves, and
    copy-on-write the block its 96-token prompt left part full."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, prefix)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [shared, rng.integers(0, vocab, max(0, n - prefix))]
                    )[:n].astype(np.int32),
                    max_new_tokens=max_new, seed=i)
            for i, n in enumerate(lengths)]


def serve_params(cfg, seed=0):
    """Random weights from ``seed`` with LSQ-initialized weight scales, made
    in one compiled program: op by op, the f32 temporaries of the
    layer-stacked MLP weights took the peak to 15.0e9 of a v5e's 16.9e9
    bytes before the engine was built."""
    make = jax.jit(lambda key: calibrate_weight_scales(
        init_params(cfg, key), parse_policy(POLICY), "lsq"))
    return make(jax.random.PRNGKey(seed))


def serve_engine(cfg, params, *, mesh=None, slots=4, block_size=64,
                 draft_layers=4, max_new=32):
    return ServeEngine(cfg, params, policy=POLICY, slots=slots,
                       cache_len=320, max_seq_len=320, num_blocks=64,
                       max_new_cap=max_new, kv_layout="paged",
                       block_size=block_size,
                       spec=SpecConfig(k=4, draft_layers=draft_layers),
                       weights_layout="w4a8", mesh=mesh)


def drain_staged(eng, reqs):
    """Serve the first request, then the rest (which share its prefix)."""
    eng.submit(reqs[0])
    eng.run_until_drained()
    for r in reqs[1:]:
        eng.submit(r)
    return eng.run_until_drained()


def serve_phase(cfg, *, block_size=64, draft_layers=4, max_new=32, seed=0):
    params = serve_params(cfg, seed)
    eng = serve_engine(cfg, params, block_size=block_size,
                       draft_layers=draft_layers, max_new=max_new)
    del params
    log(f"  peak_bytes_in_use after engine construction: {peak_bytes()}")
    reqs = shared_prefix_requests(cfg.vocab_size, max_new=max_new,
                                  seed=seed)
    t0 = time.perf_counter()
    st = drain_staged(eng, reqs)
    log(f"  served {len(reqs)} requests in {time.perf_counter() - t0:.1f} s "
        f"(compiles included): {st['tokens_out']} tokens; prefix hits "
        f"{st['prefix_hit_tokens']} tokens, {st['cow_copies']} COW copies, "
        f"{st['spec_waves']} spec waves ({st['spec_accepted']} of "
        f"{st['spec_drafted']} drafts accepted)")
    require(all(r.done and len(r.generated) == max_new for r in reqs),
            "serve: a request did not finish its token budget")
    toks = np.concatenate([np.asarray(r.generated) for r in reqs])
    require(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
            "serve: token outside the vocabulary")
    for key in ("prefix_hit_tokens", "cow_copies", "spec_waves"):
        require(st[key] > 0, f"serve: {key} is 0")
    return [tuple(r.generated) for r in reqs]


# --------------------------------------------------------------------------
# QAT
# --------------------------------------------------------------------------

def qat_phase(cfg, *, batch=4, seq=256, steps=3, seed=0):
    tcfg = TrainConfig(precision=POLICY, total_steps=steps, ref_steps=steps,
                       batch_size=batch, seq_len=seq, seed=seed)
    data = SyntheticConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                           batch_size=batch, seed=seed)
    teacher = init_params(cfg, jax.random.PRNGKey(seed))
    student = calibrate(cfg, jax.tree.map(jnp.copy, teacher), tcfg, data)
    opt = adamw_init(student)
    it = MixtureIterator(data, start_step=1)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(steps)]
    t0 = time.perf_counter()
    step_fn = jax.jit(make_train_step(cfg, tcfg),
                      donate_argnums=(0, 2)).lower(
        student, teacher, opt, batches[0], jnp.int32(0)).compile()
    mem = step_fn.memory_analysis()
    log(f"  train step compiled in {time.perf_counter() - t0:.1f} s; "
        f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
        f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")
    losses = []
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        student, opt, m = step_fn(student, teacher, opt, b, jnp.int32(i))
        losses.append(float(m["loss"]))
        log(f"  step {i}: kd loss {losses[-1]:.4f}, "
            f"{time.perf_counter() - t0:.3f} s (information only)")
    require(all(np.isfinite(losses)), f"qat: non-finite loss {losses}")
    return losses


# --------------------------------------------------------------------------
# Tensor parallelism
# --------------------------------------------------------------------------

def greedy_streams(cfg, params, mesh, *, max_new, **engine_kw):
    eng = serve_engine(cfg, params, mesh=mesh, max_new=max_new, **engine_kw)
    reqs = shared_prefix_requests(cfg.vocab_size, max_new=max_new)
    drain_staged(eng, reqs)
    require(all(r.done for r in reqs), "tp: a request did not finish")
    return [tuple(r.generated) for r in reqs], eng.stats()


def tp_parity(cfg, *, tp=4, max_new=16, seed=0, **engine_kw):
    """Greedy token streams of a tp=``tp`` engine equal the tp=1 engine's
    on the same weights and requests."""
    params = serve_params(cfg, seed)
    base, _ = greedy_streams(cfg, params, None, max_new=max_new, **engine_kw)
    gc.collect()
    got, st = greedy_streams(cfg, params, make_local_mesh(model_parallel=tp),
                             max_new=max_new, **engine_kw)
    same = sum(a == b for a, b in zip(base, got))
    log(f"  tp={st['tp_degree']} vs tp=1: {same}/{len(base)} greedy streams "
        f"identical ({sum(map(len, got))} tokens)")
    require(got == base, "tp: token streams differ from tp=1")
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp4", action="store_true",
                    help="run only the tp=4 vs tp=1 serving parity check "
                         "(needs four chips)")
    args = ap.parse_args(argv)
    try:
        dev = device_info(4 if args.tp4 else 1)
        log(f"device: {dev['kind']} x {dev['count']} ({dev['platform']})")
        log(f"compilation cache: {enable_compilation_cache()}")
        cfg = get_config(ARCH)
        if args.tp4:
            log("tp=4 parity (4 layers, published widths)")
            tp_parity(cfg.replace(n_layers=4), tp=4, draft_layers=1)
        else:
            log("kernel parity (qwen2.5-3b widths)")
            kernel_parity(cfg)
            log(f"serve ({ARCH}, {cfg.n_layers} layers, {POLICY}, w4a8, "
                f"paged, spec)")
            serve_phase(cfg)
            gc.collect()
            log(f"  peak_bytes_in_use after serve: {peak_bytes()}")
            log("qat (4 layers, batch 4 x seq 256)")
            qat_phase(cfg.replace(n_layers=4))
            log(f"  peak_bytes_in_use after qat: {peak_bytes()}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
