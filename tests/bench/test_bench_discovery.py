"""A configuration, a traffic mix and a per-layer metric are added as files
alone: the harness finds each by the name ``BENCHMARK.json`` gives it,
with no edit to a file that is already there."""
from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import bench_tiny as tiny
from bench.lib import spec, traffic

REPO = Path(__file__).resolve().parents[2]


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(tiny.with_chat(
        json.loads((REPO / "BENCHMARK.json").read_text()))))
    before = _digest(tmp_path / "bench")
    bench = tmp_path / "bench"

    # a new configuration: its file of sizes, same family as an old one
    conf = json.loads((bench / "configs" / "qwen2.5-3b.json").read_text())
    conf.update(hidden_size=1536, intermediate_size=8960,
                num_attention_heads=12, num_hidden_layers=28)
    (bench / "configs" / "newmodel.json").write_text(json.dumps(conf))
    # a new traffic mix: a data file the one generator reads
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix.update(rate_per_s=9.5)
    mix["prompt"].update(median=300)
    (bench / "traffic" / "newmix.json").write_text(json.dumps(mix))
    # a new per-layer metric: a reader of its own
    (bench / "metrics" / "new_metric.x.py").write_text(
        "def read(rec):\n    return rec['e2e']['ttft_p95_ms'] * 2\n")

    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "newmodel", "source": "https://example.org",
                         "file": "bench/configs/newmodel.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "newmodel.newmix", "config": "newmodel",
                           "traffic": "newmix", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append("newmodel.newmix")
    b["per_layer"].append({"name": "new_metric.x", "unit": "ms",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "ttft_p95_ms",
                           "workloads": ["newmodel.newmix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.cell("newmodel.newmix", tmp_path, bench_dir=bench)
    assert cell.config["hidden_size"] == 1536
    assert cell.config["name"] == "newmodel"
    assert cell.traffic["rate_per_s"] == 9.5
    assert "new_metric.x" in cell.readers
    assert cell.readers["new_metric.x"].read(
        {"e2e": {"ttft_p95_ms": 3.0}}) == 6.0
    assert {m["name"] for m in cell.end_to_end} >= {"ttft_p95_ms",
                                                   "setup_s"}
    reqs = traffic.requests(cell.traffic, 11, 5, vocab=100)
    assert reqs and all(65 <= len(r.prompt) <= 4096 for r in reqs)
    # every file that was there is unchanged
    after = _digest(bench)
    assert all(after[k] == v for k, v in before.items())
    # the cells that were there resolve as before
    old = spec.cell("qwen2.5-3b.chat", tmp_path, bench_dir=bench)
    assert "new_metric.x" not in old.readers


def test_every_named_part_of_the_benchmark_exists():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        c = spec.cell(w["name"], REPO)
        assert c.traffic["kind"] in traffic.KINDS
        assert any(m["name"] == "setup_s" for m in c.end_to_end)
        assert len(c.end_to_end) >= 2 and c.per_layer
    for c in b["configs"]:
        assert (REPO / c["file"]).is_file()
