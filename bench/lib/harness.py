"""One run of one cell: find its parts by name, check the device, set up,
measure for ``--seconds``, check what the timed path produced against the
plain reference, and print the result as the last line of stdout.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window by the readers in ``bench/metrics``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench.lib import spec

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


class CompileCounter:
    """Counts backend compiles (and persistent-cache loads) while armed."""

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def device_info(chips: int, require_chip: bool = True) -> Dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, whatever the environment says, so that only a cell's first
    run there compiles."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_lines(checks: Dict[str, Dict]) -> List[str]:
    return [f"check {k}: {v['value']!r} limit {v['limit']!r} "
            f"({'ok' if v['ok'] else 'FAIL'})" for k, v in checks.items()]


def finite(x) -> float:
    x = float(x)
    if not math.isfinite(x):
        raise ValueError(f"non-finite metric value {x}")
    return x


def run(argv: List[str], *, root: Path, t_start: float,
        require_chip: bool = True,
        override: Optional[Callable[[spec.Cell], None]] = None,
        out=None) -> int:
    """Run one cell once; returns the process exit code. ``override`` (which
    may shrink the cell's configuration and traffic) and
    ``require_chip=False`` are for the benchmark's own tests, which drive a
    run on the CPU at a small size."""
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = out or sys.stdout

    cell = spec.cell(args.workload, root)
    if override is not None:
        override(cell)
    try:
        dev = device_info(cell.chips, require_chip)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    cache = enable_compile_cache(root)
    log(f"bench: {cell.name} seed {args.seed} on {dev['kind']} x "
        f"{dev['count']}; compile cache {cache}")
    counter = CompileCounter()
    kind = cell.traffic["kind"]
    if kind == "train":
        from bench.lib import train as cells
    else:
        from bench.lib import serve as cells
    rec = cells.run_cell(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=t_start,
                         counter=counter, root=root)
    rec["device"] = dev
    rec["e2e"]["setup_s"] = rec["setup_s"]
    dev = dict(dev)
    dev["memory_peak_bytes"] = rec["memory_peak_bytes"]
    log(f"bench: setup_s {rec['setup_s']:.3f}; compiles inside the window "
        f"{rec['window_compiles']}")
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": finite(v), "unit": m["unit"]}
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]
    else:
        metrics = {m["name"]: {"value": finite(rec["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = rec["checks"]
    correct = bool(checks) and all(c["ok"] for c in checks.values())
    for line in check_lines(checks):
        log(line)
    result = {"correct": correct, "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": metrics,
              "device": dev}
    if args.trace:
        result["breakdown"] = rec["trace"]["breakdown"]
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    print(json.dumps(result), file=out, flush=True)
    return 0
