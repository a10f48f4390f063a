"""Readings from which the limits of ``correct`` are set: the numbers a
cell compares, over many seeds in one process, for sound runs, for the
control (the next precision down) and for planted faults. The benchmark's
own runs never run this.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 \\
        --seconds 5 [--variant sound|control|half_batch] [--out file]

A serving run reads both at once: the served tokens' gap under the
reference is the sound reading, and the control (the configuration's
``check.control`` policy: the reference at the next precision down picks
its own tokens at the served positions) is put in the program's place,
so its gap is the number the run compares and the run is not correct.
Training cells take the control from the program's own path at
``check.control_precision``, and the fault that leaves half of each
sequence out of the loss from a wrapped step.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import correct, harness, spec  # noqa: E402

def half_batch(step):
    """The step with the second half of every sequence left out of the
    loss: the mean is taken over the rest."""
    def run(params, teacher, opt, batch, i):
        S = batch["loss_mask"].shape[-1]
        keep = batch["loss_mask"].at[..., S // 2:].set(0.0)
        return step(params, teacher, opt, {**batch, "loss_mask": keep}, i)
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--variant", default="sound",
                    choices=("sound", "control", "half_batch"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, ROOT)
    harness.device_info(cell.chips)
    harness.enable_compile_cache(ROOT)
    counter = harness.CompileCounter()
    kind = cell.traffic["kind"]
    if kind == "train":
        from bench.lib import train as cells
        if args.variant == "control":
            cell.config["train"]["precision"] = \
                cell.config["check"]["control_precision"]
        elif args.variant == "half_batch":
            import repro.launch.steps as steps
            real = steps.make_train_step
            steps.make_train_step = lambda *a, **k: half_batch(real(*a, **k))
    else:
        from bench.lib import serve as cells
        real_checks = correct.serve_checks

        def with_control(c, seed, rec):
            return real_checks(c, seed, rec, c.config["check"]["control"])
        cells.correct.serve_checks = with_control
    out = open(args.out, "a") if args.out else sys.stdout
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        rec = cells.run_cell(cell, seed=seed, seconds=args.seconds,
                             trace=False, t_start=t0, counter=counter,
                             root=ROOT)
        line = {"workload": cell.name, "variant": args.variant,
                "seed": seed, "setup_s": rec["setup_s"],
                "checks": rec["checks"], "e2e": rec["e2e"],
                "correct": all(v["ok"] for v in rec["checks"].values()),
                "window_compiles": rec["window_compiles"],
                "memory_peak_bytes": rec["memory_peak_bytes"],
                "readings": rec.get("readings"),
                "detail": {k: v for k, v in rec.get("check_detail", {})
                           .items() if k in ("grad_worst", "change_worst",
                                             "skipped")},
                "sample": rec.get("check_sample"),
                "run_s": time.perf_counter() - t0}
        print(json.dumps(line, default=float), file=out, flush=True)
        del rec
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
