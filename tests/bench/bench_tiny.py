"""Small sizes at which the benchmark's tests drive whole runs on the CPU."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TINY_MODEL = {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "intermediate_size": 128, "vocab_size": 256,
              "num_hidden_layers": 2}


def serve(cell) -> None:
    cell.config.update(TINY_MODEL)
    cell.config["engine"].update(
        slots=4, block_size=16, num_blocks=96, max_seq_len=160,
        cache_len=160, max_new_cap=24, decode_block=4, prefill_chunk=16)
    # at this size sound runs read served gaps near 0.007 and the control
    # (A4, C4) near 0.3: the limit sits between them
    cell.config["check"].update(tokens=48, requests=3, pad_to=160,
                                positions=24, limit=0.05)
    t = cell.traffic
    t["prompt"].update(median=40, sigma=0.6, min=17, max=128)
    t["output"].update(median=10, sigma=0.5, min=4, max=24)
    t.update(rate_per_s=6.0, fill_seconds=0.5, drain_seconds=60.0)
    t["trace_seconds"] = 1.0


def train(cell) -> None:
    cell.config.update(TINY_MODEL)
    # at this size sound runs read loss gaps near 7e-6, worst-leaf
    # gradient gaps near 0.04 and change gaps near 0.07; the control
    # (A4d-C4-W4) 2.6e-4 / 0.41 / 0.12, half of each sequence left out up
    # to 1.9e-4 / 0.54 / 0.12, an unchanged state 1.0 on both norms
    cell.config["check"].update(loss_rel=5e-5, grad_norm=0.15,
                                change_norm=0.5)
    cell.traffic.update(seq_len=32)
    cell.traffic["trace_seconds"] = 1.0


CHAT_CELL = Path(__file__).with_name("chat_cell.json")


def with_chat(b: Dict) -> Dict:
    """``BENCHMARK.json``'s content with the chat cell's entries added
    (``chat_cell.json``), where it does not hold them itself."""
    frag = json.loads(CHAT_CELL.read_text())
    out = dict(b)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = {e["name"] for e in b[key]}
        out[key] = b[key] + [e for e in frag[key] if e["name"] not in names]
    return out


def chat_root(tmp: Path, repo: Path) -> Path:
    """A checkout root whose ``BENCHMARK.json`` holds the chat cell; the
    benchmark's files are the repo's own."""
    (tmp / "bench").symlink_to(repo / "bench")
    b = json.loads((repo / "BENCHMARK.json").read_text())
    (tmp / "BENCHMARK.json").write_text(json.dumps(with_chat(b)))
    return tmp
