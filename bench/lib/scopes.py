"""Device time of the QAT step under a named scope of the program that
sits inside a phase of ``bench/lib/phases.py``: the Mamba-2 mixer
(``mamba``, around each mixer in ``models/model.py``) and its chunked scan
(``ssd``, in ``models/recurrent.mamba2_fwd``), forward and backward.

An operation counts to every scope in its own ``op_name``, so ``ssd``
counts again inside ``mamba`` and ``mamba`` inside ``layers``. As in
``phases``, a fusion counts whole by the ``op_name`` of the fusion
instruction, whatever is fused inside it. The trace is the one the window
left (``phases.TRACE_DIR``), reduced once per run; a program without the
scopes reads nothing.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, Optional

from bench.lib import phases, readers, trace, xspace

SCOPES = ("mamba", "ssd")


def reduce_ops(planes, op_names, program: str = readers.TRAIN) -> Dict:
    """Device seconds of ``program``'s operations under each of
    :data:`SCOPES`, its time and its count of runs, averaged over the
    device planes."""
    devs = sorted(p for p in planes if p.startswith(trace.DEVICE_PREFIX))
    if not devs:
        raise ValueError(f"no device planes in the trace: {sorted(planes)}")
    k = 1e-9 / len(devs)
    secs = dict.fromkeys(SCOPES, 0.0)
    total, calls = 0.0, 0
    for p in devs:
        mods = sorted((s, s + d, n) for n, s, d in
                      planes[p].get(trace.MODULES_LINE, [])
                      if trace.program_name(n) == program)
        total += sum(e - s for s, e, _ in mods) * k
        calls += len(mods)
        starts = [m[0] for m in mods]
        for n, s, d in planes[p].get(trace.OPS_LINE, []):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1] or any(c in n for c in
                                               trace.CONTAINERS):
                continue
            names = set(phases.scopes(op_names.get(mods[i][2], {}).get(
                xspace.instruction(n), "")))
            for scope in SCOPES:
                if scope in names:
                    secs[scope] += d * k
    return {"seconds": secs, "program_s": total,
            "calls": calls // len(devs)}


def run_ms(rec: Dict, trace_dir: Optional[Path] = None
           ) -> Optional[Dict[str, float]]:
    """Milliseconds per step under each scope that holds any operation,
    reduced once and kept in ``rec``; None where the trace is not the
    run's (its step time differs from what the run reduced)."""
    if "scope_ms" not in rec:
        rec["scope_ms"] = _run_ms(rec, Path(trace_dir or phases.TRACE_DIR))
    return rec["scope_ms"]


def _run_ms(rec: Dict, trace_dir: Path) -> Optional[Dict[str, float]]:
    files = sorted(trace_dir.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    want = (rec.get("trace") or {}).get("programs", {}).get(readers.TRAIN)
    if not files or not want:
        return None
    red = reduce_ops(trace.load(files[-1]), xspace.op_names(files[-1]))
    n = red["calls"]
    if not n or abs(red["program_s"] - want) > 1e-9 * n:
        return None
    return {s: 1e3 * v / n for s, v in red["seconds"].items() if v > 0}
