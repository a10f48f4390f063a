"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.
A reader returns a number, or None where its run has nothing to read."""
from __future__ import annotations

from typing import Dict, Optional

from bench.lib import stats as st


def due_to_event_p95_ms(rec: Dict, event: str) -> Optional[float]:
    """p95 over requests of (engine event time - the request's due time)."""
    due = rec.get("uid_due", {})
    waits = [(e["t"] - due[e["uid"]]) * 1e3
             for e in rec.get("tracer_events", [])
             if e.get("ph") == "event" and e["name"] == event
             and e["uid"] in due]
    return st.percentile(waits, 95) if waits else None


def delta(rec: Dict, key: str) -> float:
    """Change of an engine counter over the traced interval."""
    c = rec["counters"]
    return float(c["end"][key]) - float(c["start"][key])


def seconds_of(table: Dict[str, float], *patterns: str) -> float:
    """Device seconds of the entries whose name holds any pattern."""
    return sum(v for k, v in table.items() if any(p in k for p in patterns))


def idle_share_pct(rec: Dict) -> Optional[float]:
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


DECODE = "jit__decode_chunk"
PREFILL = ("jit__tail_wave", "jit__admit_batch")
TRAIN = "jit_train_step"


def is_w4a8(op: str) -> bool:
    """A call of the packed-int4 x int8 matmul kernel: a custom call that
    takes the uint8 nibble-packed weights beside int8 activations."""
    return "custom-call(" in op and " u8[" in op and "s8[" in op


def is_paged_attn(op: str) -> bool:
    """A call of the paged decode attention kernel: a custom call, led by
    the int32 block table, that reads the int8 pool."""
    head = op.split("custom-call(", 1)
    return len(head) == 2 and head[1].startswith("s32[") and "s8[" in head[1]


def kernel_seconds(rec: Dict, program: str, pred) -> float:
    ops = rec["trace"]["by_program"].get(program, {})
    return sum(v for k, v in ops.items() if pred(k))


def traced(rec: Dict, t: float) -> bool:
    t0, t1 = rec["trace_host"]
    return t0 <= t < t1


def tail_waves(rec: Dict):
    """(rows, prompt tokens) of every tail wave in the traced interval,
    from the engine's own spans."""
    return [(e["args"]["rows"], e["args"]["tokens"])
            for e in rec.get("tracer_events", [])
            if e.get("ph") == "span" and e["name"] == "tail_wave"
            and traced(rec, e["t0"])]


def decode_contexts(rec: Dict):
    """Context length of every token decoded in the traced interval (the
    first token of a request comes from its prefill)."""
    out = []
    for t in rec["timelines"]:
        plen, j = len(t.req.prompt), 0
        for ts, n in t.chunks:
            for _ in range(n):
                if j > 0 and traced(rec, ts):
                    out.append(plen + j)
                j += 1
    return out
