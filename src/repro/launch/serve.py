"""Batched quantized serving driver (continuous-batching engine v2).

Loads (or initializes) a model, deploys it at the given precision, and
drives the slot-based ServeEngine three ways:

* default — closed-loop batch: submit every synthetic request up front,
  drain, report throughput/TTFT.
* ``--arrival-rate R`` — open-loop: Poisson arrivals at R req/s through
  the asyncio frontend, optionally with a first-token SLO
  (``--deadline-ms`` + ``--shed``), reporting SLO attainment and
  goodput alongside the engine stats.
* ``--http-port P`` — serve: start the OpenAI-style HTTP endpoint
  (``/v1/completions`` with SSE streaming; see docs/serving_api.md) and
  run until interrupted.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.launch.cache import enable_compilation_cache
from repro.models import init_params
from repro.serve.engine import Request, ServeEngine


def build_requests(args, cfg) -> list:
    rng = np.random.default_rng(0)
    reqs = []
    for uid in range(args.requests):
        plen = args.prompt_len
        if args.vary_prompts:
            plen = int(rng.integers(max(4, plen // 2), plen + 1))
        reqs.append(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=args.temperature,
            top_k=args.top_k,
            seed=uid))
    return reqs


def run_open_loop(args, engine, cfg):
    """Poisson arrivals at ``--arrival-rate`` req/s through the asyncio
    frontend; returns (engine stats + SLO metrics, wall seconds).

    Runs the workload twice: an untimed warmup pass (open-loop arrivals
    hit XLA compile variants — small admission waves — that a batch
    drain never triggers; a cold pass would blame multi-second compile
    stalls on the SLO) and then the identical timed pass."""
    import asyncio

    from repro.serve.frontend import AsyncFrontend

    deadline_ms = args.deadline_ms or None

    async def one_pass():
        rng = np.random.default_rng(1)
        t0 = time.perf_counter()
        async with AsyncFrontend(engine,
                                 default_deadline_ms=deadline_ms) as fe:
            handles = []
            for req in build_requests(args, cfg):
                await asyncio.sleep(rng.exponential(1.0 / args.arrival_rate))
                handles.append(await fe.submit(
                    req.prompt, max_new_tokens=req.max_new_tokens,
                    temperature=req.temperature, top_k=req.top_k,
                    seed=req.seed))
            for h in handles:
                await h.tokens()
            stats = await fe.stats()
        return handles, stats, time.perf_counter() - t0

    async def go():
        print("warmup pass (compiling open-loop admission variants)...")
        await one_pass()
        engine.reset()
        handles, stats, wall = await one_pass()
        shed = sum(1 for h in handles if h.shed)
        ttfts = sorted(h.first_token_t - h.submit_t for h in handles
                       if not h.shed and h.first_token_t is not None)
        stats["arrival_rate_rps"] = args.arrival_rate
        if deadline_ms is not None:
            met = sum(1 for t in ttfts if t <= deadline_ms / 1e3)
            stats["slo_attainment"] = met / max(len(handles), 1)
            stats["goodput_rps"] = met / max(wall, 1e-9)
            print(f"open loop @ {args.arrival_rate:.1f} req/s: "
                  f"{met}/{len(handles)} met the {deadline_ms:.0f} ms "
                  f"first-token SLO ({shed} shed), goodput "
                  f"{stats['goodput_rps']:.2f} req/s")
        else:
            print(f"open loop @ {args.arrival_rate:.1f} req/s: "
                  f"{len(handles)} served, {shed} shed")
        return stats, wall

    return asyncio.run(go())


def run_http(args, engine):
    """Serve the OpenAI-style HTTP endpoint until interrupted."""
    import asyncio

    from repro.serve.frontend import AsyncFrontend
    from repro.serve.http import ServeHTTP

    async def go():
        async with AsyncFrontend(
                engine, default_deadline_ms=args.deadline_ms or None) as fe:
            async with ServeHTTP(fe, host=args.http_host,
                                 port=args.http_port) as srv:
                print(f"serving on http://{args.http_host}:{srv.port} "
                      f"(POST /v1/completions, GET /v1/stats, "
                      f"/v1/metrics, /health; Ctrl-C to stop)")
                await srv.serve_forever()

    try:
        asyncio.run(go())
    except KeyboardInterrupt:
        print("\nshutting down")


def write_obs(args, engine, stats=None):
    """``--trace`` / ``--metrics`` epilogue shared by all three drive
    modes (closed-loop drain, open-loop arrivals, HTTP serve)."""
    if args.trace:
        from repro.obs.export import write_trace
        write_trace(args.trace, engine.trace,
                    compile_variants=engine.wave_variant_signatures())
        n_spans = sum(1 for e in engine.trace.events()
                      if e["ph"] == "span")
        print(f"wrote {args.trace}: {len(engine.trace)} trace records "
              f"({n_spans} spans, {engine.trace.dropped} dropped) — load "
              f"at ui.perfetto.dev or run: python tools/trace_report.py "
              f"{args.trace}")
    if args.metrics:
        print(engine.metrics.render(stats if stats is not None
                                    else engine.stats()), end="")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--policy", default="A8d-C8-W4")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--vary-prompts", action="store_true",
                    help="draw prompt lengths in [prompt_len/2, prompt_len]")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--decode-block", default="8",
                    help="decode steps per compiled on-device chunk; "
                         "'auto' probes decode-step latency at startup. "
                         "With speculative decoding active (the paged "
                         "default — see --no-spec) the draft+verify wave "
                         "owns step granularity instead: this knob is "
                         "overridden to spec-k+1 and the 'auto' probe is "
                         "skipped, so pass --no-spec to make it (or the "
                         "probe) take effect")
    ap.add_argument("--kv-layout", default="dense",
                    choices=("dense", "paged"),
                    help="paged = block-table KV cache with free-block "
                         "admission and chunked prefill")
    ap.add_argument("--block-size", type=int, default=64,
                    help="tokens per cache block (paged layout)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool size in blocks (0 = match the dense "
                         "slots*cache_len budget)")
    ap.add_argument("--max-seq-len", type=int, default=0,
                    help="per-request token cap / block-table width "
                         "(paged; 0 = match the dense cache_len)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prefix sharing (paged; on by default: "
                         "prompts extending a cached prefix map the same "
                         "pool blocks and prefill only their tail)")
    ap.add_argument("--admission", default="reserve",
                    choices=("reserve", "optimistic"),
                    help="paged admission: reserve worst-case blocks up "
                         "front, or admit on prompt footprint and preempt "
                         "(swap out) a resident when the pool runs dry")
    ap.add_argument("--tail-batch", type=int, default=0,
                    help="max tail/chunked prefills advanced per batched "
                         "wave (0 = every slot, 1 = serialized legacy "
                         "path)")
    ap.add_argument("--no-prefix-affinity", action="store_true",
                    help="disable chain-grouped scheduling of prefix-hit "
                         "requests")
    ap.add_argument("--preempt", default="last_admitted",
                    choices=("last_admitted", "longest_remaining"),
                    help="victim policy for optimistic-admission "
                         "preemption")
    ap.add_argument("--no-spec", action="store_true",
                    help="disable speculative decoding (paged layout "
                         "enables it by default: a truncated-layer draft "
                         "proposes k tokens per slot and the target "
                         "verifies every resident's drafts in one "
                         "compiled wave, rolling rejected suffixes back)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per slot per verify-wave")
    ap.add_argument("--spec-draft", type=int, default=0,
                    help="draft depth in layers (0 = half the target's "
                         "layers; equal to n_layers = self-draft)")
    ap.add_argument("--spec-accept", default="exact",
                    choices=("exact", "rejection"),
                    help="acceptance rule: 'exact' commits the target's "
                         "own samples (output identical to plain decode); "
                         "'rejection' runs speculative rejection sampling "
                         "for temperature/top-k requests")
    ap.add_argument("--sched", default="fcfs",
                    choices=("fcfs", "sjf", "edf"),
                    help="admission order: arrival, shortest-prompt, or "
                         "earliest-deadline-first within priority class "
                         "(pair edf with --deadline-ms / --shed)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop mode: Poisson arrivals at this many "
                         "requests/s through the asyncio frontend "
                         "(0 = closed-loop batch, the default)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request first-token SLO in ms (open-loop / "
                         "HTTP modes; 0 = no deadline). With --shed the "
                         "engine rejects or downgrades requests predicted "
                         "to miss it")
    ap.add_argument("--shed", default="none",
                    choices=("none", "reject", "downgrade"),
                    help="SLO admission control when a queued request's "
                         "predicted TTFT exceeds its deadline: drop it "
                         "(reject) or clear its deadline and demote it "
                         "behind on-time work (downgrade)")
    ap.add_argument("--http-port", type=int, default=0,
                    help="serve mode: bind the OpenAI-style HTTP endpoint "
                         "(/v1/completions with SSE streaming) on this "
                         "port and run until interrupted (0 = off)")
    ap.add_argument("--http-host", default="127.0.0.1")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: builds a local "
                         "('data', 'model') mesh over the visible devices "
                         "and serves every wave sharded across it")
    ap.add_argument("--weights", default="bf16", choices=("bf16", "w4a8"),
                    help="serve weight layout: bf16 fake-quant einsums, or "
                         "w4a8 packed-int4 weights x dynamic-int8 "
                         "activations through the deployment matmul "
                         "(Pallas on TPU, XLA ref elsewhere)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="record a runtime trace and write it here as "
                         "Chrome/Perfetto trace_event JSON (open at "
                         "ui.perfetto.dev; summarize with "
                         "tools/trace_report.py). Open-loop runs trace "
                         "the timed pass only (the warmup's records are "
                         "cleared by the engine reset)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus text /v1/metrics serves "
                         "at the end of the run")
    ap.add_argument("--bench-out", default="",
                    help="write the run's stats to this JSON file")
    args = ap.parse_args()
    enable_compilation_cache()

    cfg = (get_config if args.full else get_reduced_config)(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    decode_block = (args.decode_block if args.decode_block == "auto"
                    else int(args.decode_block))
    kw = {}
    if args.kv_layout == "paged":
        kw = {"kv_layout": "paged", "block_size": args.block_size,
              "num_blocks": args.num_blocks or None,
              "max_seq_len": args.max_seq_len or None,
              "prefix_cache": not args.no_prefix_cache,
              "admission": args.admission, "preempt": args.preempt,
              "tail_batch": args.tail_batch,
              "prefix_affinity": not args.no_prefix_affinity}
        if not args.no_spec:
            from repro.serve.spec import SpecConfig
            kw["spec"] = SpecConfig(k=args.spec_k,
                                    draft_layers=args.spec_draft or None,
                                    accept_mode=args.spec_accept)
    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(model_parallel=args.tp)
    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer
        tracer = Tracer()
    engine = ServeEngine(cfg, params, policy=args.policy, slots=args.slots,
                         cache_len=args.cache_len,
                         decode_block=decode_block,
                         sched_policy=args.sched, slo_shed=args.shed,
                         max_new_cap=max(32, args.max_new),
                         weights_layout=args.weights, trace=tracer,
                         mesh=mesh, **kw)
    if mesh is not None:
        st0 = engine.stats()
        print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} devices, "
              f"tp={engine.tp}; per device: "
              f"{st0['per_device_pool_bytes'] / 1e6:.2f} MB KV pool, "
              f"{st0['per_device_weight_bytes'] / 1e6:.2f} MB weights")
    if args.http_port:
        run_http(args, engine)
        write_obs(args, engine)
        return
    if args.arrival_rate > 0:
        stats, dt = run_open_loop(args, engine, cfg)
    else:
        for req in build_requests(args, cfg):
            engine.submit(req)
        t0 = time.perf_counter()
        stats = engine.run_until_drained()
        dt = time.perf_counter() - t0
    stats["wall_s"] = dt
    stats["tok_s"] = stats["tokens_out"] / max(dt, 1e-9)
    print(f"served {args.requests} requests in {dt:.2f}s: "
          f"{stats['tokens_out']} tokens, {stats['tok_s']:.1f} tok/s, "
          f"{stats['decode_steps']} decode steps "
          f"({stats['decode_step_s'] * 1e3:.1f} ms/step), "
          f"TTFT p50 {stats['ttft_p50_s'] * 1e3:.0f} ms "
          f"p95 {stats['ttft_p95_s'] * 1e3:.0f} ms")
    if stats["weights_layout"] == "w4a8":
        print(f"weights: w4a8 packed, "
              f"{stats['packed_weight_bytes'] / 1e6:.2f} MB streamed per "
              f"forward ({stats['weight_hbm_saved_bytes'] / 1e6:.2f} MB "
              f"bf16 HBM traffic saved)")
    if args.kv_layout == "paged":
        print(f"prefix cache: {stats['prefix_hit_tokens']} hit tokens / "
              f"{stats['prompt_tokens_prefilled']} prefilled, "
              f"{stats['cow_copies']} COW copies; preemption: "
              f"{stats['preemptions']} swaps, "
              f"{stats['swap_out_bytes'] + stats['swap_in_bytes']} bytes "
              f"moved in {stats['swap_s'] * 1e3:.0f} ms")
        if "spec_waves" in stats:
            print(f"speculative: {stats['spec_waves']} waves, "
                  f"{stats['spec_drafted']} drafted / "
                  f"{stats['spec_accepted']} accepted / "
                  f"{stats['spec_rolled_back']} rolled back "
                  f"(accept rate {stats['spec_accept_rate']:.2f}, "
                  f"k={stats['spec_k']}, "
                  f"draft {stats['spec_draft_layers']} layers)")
    write_obs(args, engine, stats)
    if args.bench_out:
        with open(args.bench_out, "w") as f:
            json.dump({"args": vars(args), "stats": stats}, f, indent=2)
        print(f"wrote {args.bench_out}")


if __name__ == "__main__":
    main()
