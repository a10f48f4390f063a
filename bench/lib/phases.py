"""Device time of the QAT step by phase, from the named scopes the program
opens in it (``launch.steps.make_train_step``, ``models.model.forward``).

JAX carries every ``jax.named_scope`` into the ``op_name`` metadata of
the HLO instructions it lowers to, the backward pass's included
(``jit(train_step)/transpose(jvp(layers))/while/...``). A TPU trace's
operation events carry no ``op_name``; it is looked up by instruction
name in the program's HLO proto, which the profiler stores in the same
trace (``bench/lib/xspace.py``). An operation belongs to the phase of
the innermost scope of :data:`PHASES` in its own ``op_name``; a fusion
counts whole, by the ``op_name`` XLA gives the fusion instruction, even
where instructions inside it come from another scope. ``unscoped``
is the rest of the step's device time: its operations under none of
those scopes (layout copies XLA adds without metadata, ops whose
``op_name`` lost its path) and the time between operations inside the
step, so the four phases add up to the step's time.

The reduction re-reads the profiler trace the window left in
``.bench_trace`` at the checkout's root, beside ``trace.reduce_planes``,
which keeps no ``op_name``. A trace whose step holds no scoped operation
(a program without the scopes) reads nothing.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.lib import readers, trace, xspace

# phase -> the named scopes it gathers
PHASES = {"layers": ("layers",),
          "vocab": ("embed", "head", "kd_loss"),
          "optimizer": ("optimizer",)}
UNSCOPED = "unscoped"
SCOPE_PHASE = {s: ph for ph, scopes in PHASES.items() for s in scopes}

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"

_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def scopes(op_name: str) -> List[str]:
    """The scope names of an ``op_name``, outermost first, with JAX's
    transformation wrappers taken off: ``transpose(jvp(layers))`` ->
    ``layers``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def phase(op_name: str) -> str:
    for s in reversed(scopes(op_name)):
        if s in SCOPE_PHASE:
            return SCOPE_PHASE[s]
    return UNSCOPED


def reduce_ops(planes: Dict[str, Dict[str, List[Tuple[str, int, int]]]],
               op_names: Dict[str, Dict[str, str]]) -> Dict:
    """``planes`` as ``trace.load`` gives them; ``op_names``: program (as
    its ``XLA Modules`` events name it) -> instruction -> ``op_name``.
    Returns, averaged over the device planes as ``trace.reduce_planes``
    averages: device seconds per program and phase (``by_phase``) of
    operations that hold no others, and each program's time
    (``programs``) and count of runs (``calls``)."""
    devs = sorted(p for p in planes if p.startswith(trace.DEVICE_PREFIX))
    if not devs:
        raise ValueError(f"no device planes in the trace: {sorted(planes)}")
    k = 1e-9 / len(devs)
    by_phase: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    programs: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    for p in devs:
        mods = sorted((s, s + d, n) for n, s, d in
                      planes[p].get(trace.MODULES_LINE, []))
        for s, e, n in mods:
            programs[trace.program_name(n)] += (e - s) * k
            calls[trace.program_name(n)] += 1
        starts = [m[0] for m in mods]
        for n, s, d in planes[p].get(trace.OPS_LINE, []):
            if any(c in n for c in trace.CONTAINERS):
                continue
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            op_name = op_names.get(module, {}).get(xspace.instruction(n), "")
            by_phase[trace.program_name(module)][phase(op_name)] += d * k
    return {"by_phase": {m: dict(v) for m, v in by_phase.items()},
            "programs": dict(programs), "calls": dict(calls)}


def step_ms(red: Dict, program: str = readers.TRAIN
            ) -> Optional[Dict[str, float]]:
    """Milliseconds per run of ``program`` in each phase, ``unscoped``
    being the program's time outside the scoped phases' operations; None
    where the program did not run or none of its operations is scoped."""
    n = red["calls"].get(program, 0)
    ph = red["by_phase"].get(program, {})
    scoped = sum(ph.get(p, 0.0) for p in PHASES)
    if not n or scoped <= 0:
        return None
    out = {p: 1e3 * ph.get(p, 0.0) / n for p in PHASES}
    out[UNSCOPED] = 1e3 * (red["programs"][program] - scoped) / n
    return out


def reduce_file(path: Path) -> Dict:
    return reduce_ops(trace.load(path), xspace.op_names(path))


def run_ms(rec: Dict, trace_dir: Optional[Path] = None
           ) -> Optional[Dict[str, float]]:
    """``step_ms`` of the run's traced window, reduced once and kept in
    ``rec``: the newest trace under ``trace_dir`` (``TRACE_DIR``), where
    it is the trace the run reduced (the same time of the step, to a
    nanosecond a run)."""
    if "phase_ms" not in rec:
        rec["phase_ms"] = _run_ms(rec, Path(trace_dir or TRACE_DIR))
    return rec["phase_ms"]


def _run_ms(rec: Dict, trace_dir: Path) -> Optional[Dict[str, float]]:
    files = sorted(trace_dir.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    want = (rec.get("trace") or {}).get("programs", {}).get(readers.TRAIN)
    if not files or not want:
        return None
    red = reduce_file(files[-1])
    n = red["calls"].get(readers.TRAIN, 0)
    if not n or abs(red["programs"][readers.TRAIN] - want) > 1e-9 * n:
        return None
    return step_ms(red)
