"""Finds a cell's parts by name: ``BENCHMARK.json`` at the checkout's
root names the workload's configuration and traffic mix, and each lives
in a file of its own (``bench/configs``, ``bench/traffic``); every
per-layer metric is a reader in ``bench/metrics/<name>.py``."""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path
    run_seconds: int = 10
    readers: Dict[str, ModuleType] = field(default_factory=dict)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file by path (metric and family files carry names that
    are not Python identifiers, such as ``decode_step_ms.chat``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader "
                                f"at {path}")
    return load_module(path, f"bench_metric_{name.replace('.', '_')}")


def cell(workload: str, root: Path, bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve ``workload`` in ``root/BENCHMARK.json``."""
    b = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in b["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in b["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    config.setdefault("name", conf["name"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    c = Cell(name=workload, chips=int(w["chips"]), config=config,
             traffic=traffic,
             end_to_end=[m for m in b["end_to_end"] if _applies(m, workload)],
             per_layer=[m for m in b["per_layer"] if _applies(m, workload)],
             root=root, run_seconds=int(b["run_seconds"]))
    c.readers = {m["name"]: reader(m["name"], bench_dir)
                 for m in c.per_layer}
    return c


def family(kind: str, name: str) -> ModuleType:
    """A model family's module: ``kind`` is ``reference`` (the plain
    reference) or ``adapters`` (the bridge to the program)."""
    return importlib.import_module(f"bench.{kind}.{name}")
