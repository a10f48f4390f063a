"""Sweep of open-loop arrival rates on one engine, to find a serving cell's
knee once: the highest rate at which completions keep pace with arrivals
and the backlog does not grow across the window. The benchmark's own runs
never search for a rate; the rate found here goes into the mix's file.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 40 \\
        --rates 0.5,0.75,1.0
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness, serve, spec  # noqa: E402
from bench.lib import trace as reduce  # noqa: E402
from bench.lib import traffic as tr  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--fill", type=float, default=40.0,
                    help="seconds of arrivals before each window")
    ap.add_argument("--drain", type=float, default=150.0,
                    help="longest wait for the window's requests to finish")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, ROOT)
    harness.device_info(cell.chips)
    harness.enable_compile_cache(ROOT)
    counter = harness.CompileCounter()
    eng, cfg = serve.build(cell, args.seed, trace=False)
    client = serve.Client(eng, cfg.vocab_size)
    serve.warm_up(client, eng.slots, eng.prefill_chunk, cell.traffic)
    harness.log(f"sweep: set-up {time.perf_counter() - T_START:.1f} s")
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate, fill_seconds=args.fill,
                   drain_seconds=args.drain)
        tls = [serve.Timeline(req=r) for r in
               tr.requests(mix, args.seed + k, args.seconds, cfg.vocab_size)]
        w = serve.open_loop(client, tls, args.seconds, mix, counter,
                            reduce.Window(ROOT, False, 0))
        sent = [t for t in tls if t.sent is not None]
        e2e, attempted, failed = serve.client_metrics(
            sent, w["w0"], w["w1"])
        finished = sum(1 for t in sent if t.done and t.last is not None
                       and w["w0"] <= t.last < w["w1"])
        print(json.dumps({"rate_per_s": rate, "attempted": attempted,
                          "failed": failed, "finished_in_window": finished,
                          "backlog": w["backlog"],
                          "window_compiles": counter.count, **e2e}),
              flush=True)
        # drop what is still queued or resident; compiled programs stay
        eng.reset()
        client.outstanding = 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
