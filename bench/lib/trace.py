"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per device, the union of the intervals in which an operation
ran (busy time), the device time of each compiled program and of each
operation, and the idle gaps between operations with what the host was
doing in each (the benchmark's own ``TraceAnnotation`` spans).

Read with ``jax.profiler.ProfileData`` alone, so every PR reduces a trace
the same way.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."

Interval = Tuple[int, int]          # (start_ns, end_ns)
# operations that hold others (a loop and its body): counted in busy time,
# never in an operation's own time
CONTAINERS = (" while(", " conditional(", " call(")


def program_name(module: str) -> str:
    """A compiled program's name without the hash JAX appends to it:
    ``jit__decode_chunk(1599...)`` -> ``jit__decode_chunk``."""
    return module.split("(", 1)[0]


def short(op: str, n: int = 120) -> str:
    return op if len(op) <= n else op[:n] + "..."


def union_ns(intervals: Sequence[Interval]) -> int:
    """Total length covered by the union of half-open intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi) not covered by ``intervals``."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def innermost(spans: Sequence[Tuple[int, int, str]], t: int) -> str:
    """Name of the shortest host span that contains time ``t``."""
    best, best_len = "host:outside bench spans", None
    for s, e, name in spans:
        if s <= t < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce_planes(planes: Dict[str, Dict[str, List[Tuple[str, int, int]]]]
                  ) -> Dict:
    """``planes``: plane name -> line name -> [(event name, start_ns,
    duration_ns)]. Returns, averaged over the device planes: busy and
    window seconds, device seconds per program, per operation and per
    (program, operation), and the ten longest idle gaps named by the host
    span they fall in. The window is the span of the benchmark's own host
    annotations where the trace has them, else of the device's events."""
    devs = sorted(p for p in planes if p.startswith(DEVICE_PREFIX))
    if not devs:
        raise ValueError(f"no device planes in the trace: {sorted(planes)}")
    host = [(s, s + d, n) for p, lines in planes.items()
            if not p.startswith("/device:") for evs in lines.values()
            for (n, s, d) in evs if n.startswith(HOST_SPAN_PREFIX)]
    k = 1e-9 / len(devs)
    busy, window = [], []
    programs: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    by_program: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    all_gaps: List[Tuple[str, float]] = []
    for p in devs:
        op_ev = planes[p].get(OPS_LINE, [])
        mods = sorted((s, s + d, program_name(n)) for n, s, d in
                      planes[p].get(MODULES_LINE, []))
        for s, e, n in mods:
            programs[n] += (e - s) * k
            calls[n] += 1
        starts = [m[0] for m in mods]
        ivs = []
        for n, s, d in op_ev:
            ivs.append((s, s + d))
            if any(c in n for c in CONTAINERS):
                continue
            ops[n] += d * k
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            by_program[owner][n] += d * k
        if host:
            lo = min(s for s, _, _ in host)
            hi = max(e for _, e, _ in host)
        elif ivs:
            lo, hi = min(s for s, _ in ivs), max(e for _, e in ivs)
        else:
            lo = hi = 0
        busy.append(union_ns([(max(s, lo), min(e, hi)) for s, e in ivs
                              if e > lo and s < hi]) * 1e-9)
        window.append((hi - lo) * 1e-9)
        for s, e in gaps(ivs, lo, hi):
            all_gaps.append((innermost(host, (s + e) // 2), (e - s) * 1e-9))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(all_gaps, key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / len(busy),
            "window_s": sum(window) / len(window),
            "programs": dict(programs), "calls": dict(calls),
            "ops": dict(ops),
            "by_program": {m: dict(v) for m, v in by_program.items()},
            "breakdown": {"device_ops": [[short(n), v] for n, v in top_ops],
                          "idle_gaps": [list(x) for x in top_gaps]}}


def profile_options():
    """Device and host activity, with the Python tracer off: it would
    trace every Python call of the loop and slow the host it measures."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


class Window:
    """The profiler over the first ``seconds`` of a measured window, on
    ``--trace 1`` runs only. ``mark`` (optional) is called as the profiler
    starts and as it stops; what it returns is kept for the readers."""

    def __init__(self, root: Path, on: bool, seconds: float,
                 mark: Optional[Callable[[], Dict]] = None):
        self.on = on
        self.dir = Path(root) / ".bench_trace"
        self.len = float(seconds)
        self.mark = mark or dict
        self.t0 = self.t1 = None
        self.marks: List[Dict] = []

    def start(self, now: float) -> None:
        if not self.on:
            return
        import shutil
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.marks.append(self.mark())
        jax.profiler.start_trace(str(self.dir),
                                 profiler_options=profile_options())
        self.t0 = now

    def maybe_stop(self, now: float) -> bool:
        """Stop once the traced length has passed; True when it stopped
        now."""
        if self.on and self.t1 is None and now - self.t0 >= self.len:
            self.stop()
            return True
        return False

    def stop(self) -> None:
        if not self.on or self.t1 is not None:
            return
        import time
        import jax
        jax.effects_barrier()
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.marks.append(self.mark())

    def record(self) -> Dict:
        if not self.on:
            return {}
        return {"trace": reduce_dir(self.dir),
                "trace_host": (self.t0, self.t1), "marks": self.marks}


def load(path: Path) -> Dict[str, Dict[str, List[Tuple[str, int, int]]]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: Dict[str, Dict[str, List[Tuple[str, int, int]]]] = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in line.events)
    return out


def reduce_dir(d: Path) -> Dict:
    """Reduce the newest trace written under ``d``."""
    files = sorted(Path(d).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return reduce_planes(load(files[-1]))
