"""Serving cells: the program's ``ServeEngine`` driven through its public
``submit``/``step`` calls, open loop: requests are sent at their due
times whatever the engine's progress.

Times come from the host clock around the engine's calls. Each request's
first token is the time its first tokens reach the client (the engine's
``on_tokens`` stream); TTFT runs from the time the request was due, so a
stalled loop shows in every request behind it.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.lib import correct, spec
from bench.lib import stats as st
from bench.lib import trace as reduce
from bench.lib import traffic as tr
from bench.lib.harness import log, memory_peak_bytes


@dataclass
class Timeline:
    """What the client saw of one request."""
    req: tr.Req
    due: float = 0.0                 # host clock
    sent: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    chunks: List = field(default_factory=list)   # (time, n tokens)
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    counted: bool = False            # due inside the window


def _pow2_upto(n: int) -> List[int]:
    out, k = [], 1
    while k < n:
        out.append(k)
        k *= 2
    return out + [n]


class Client:
    """Sends requests to the engine and records what comes back."""

    def __init__(self, engine, vocab: int):
        from repro.serve.engine import Request
        from jax.profiler import TraceAnnotation
        self._Request = Request
        self.span = TraceAnnotation
        self.eng = engine
        self.vocab = vocab
        self.outstanding = 0
        self.uid = 0
        self.uid_due: Dict[int, float] = {}

    def send(self, tl: Timeline, now: float) -> None:
        r = tl.req

        def on_tokens(_req, toks, done, tl=tl):
            t = time.perf_counter()
            if toks:
                if tl.first is None:
                    tl.first = t
                tl.last = t
                tl.chunks.append((t, len(toks)))
                tl.tokens.extend(int(x) for x in toks)
            if done:
                tl.done = True
                self.outstanding -= 1

        self.uid += 1
        self.uid_due[self.uid] = tl.due
        self.eng.submit(self._Request(
            uid=self.uid, prompt=r.prompt, max_new_tokens=r.max_new,
            temperature=r.temperature, top_k=r.top_k, seed=r.seed,
            on_tokens=on_tokens))
        tl.sent = now
        self.outstanding += 1

    def step(self) -> None:
        with self.span("bench.engine_step"):
            self.eng.step()


def first_token_groups(slots: int) -> List[List[Tuple[int, bool]]]:
    """Groups of requests that, sent together, make the first-token path
    run at every (padded rows, rows finishing) pair, and at every count
    of rows finishing both all greedy and not. A group is a list of
    waves ``(k, greedy)``: its rows all start together, and in its i-th
    wave after the first, ``k`` of the rows still prefilling finish."""
    pad = lambda n: min(1 << (n - 1).bit_length(), slots)   # noqa: E731
    pairs = {(p, k) for p in _pow2_upto(slots) for k in range(1, p + 1)}
    flags = {(k, g) for k in range(1, slots + 1) for g in (True, False)}

    def wanted(n):
        return [k for k in range(1, n + 1) if (pad(n), k) in pairs]

    groups = []
    while pairs:
        n = max(m for m in range(1, slots + 1) if wanted(m))
        waves = []
        while n:
            ks = wanted(n)
            # the fewest rows that leave the rest something to cover, else
            # the most; with nothing to cover here, enough rows to reach
            # a count that has something
            k = next((k for k in ks if k < n and any(
                wanted(m) for m in range(1, n - k + 1))), None)
            if k is None:
                k = max(ks) if ks else n - max(
                    [m for m in range(1, n) if wanted(m)] or [0])
            pairs.discard((pad(n), k))
            g = (k, True) in flags
            flags.discard((k, g))
            waves.append((k, g))
            n -= k
        groups.append(waves)
    groups += [[(k, g)] for k, g in sorted(flags)]
    return groups


def warm_up(client: Client, slots: int, C: int, mix: Dict) -> None:
    """Run every compiled shape the cell's traffic can reach, so that
    nothing compiles in the window: the decode chunk with and without
    sampled rows; the tail wave at every padded row count and every
    history bucket the longest prompt reaches; and the first-token path
    at every (padded rows, rows finishing) pair (``first_token_groups``)."""
    rng = np.random.default_rng(0)
    pmax = mix["prompt"]["max"]

    def group(rows):
        """``rows``: (prompt length, greedy) of requests sent together."""
        for i, (n, g) in enumerate(rows):
            tl = Timeline(req=tr.Req(
                index=-1, prompt=rng.integers(0, client.vocab, n)
                .astype(np.int32), max_new=2,
                temperature=0.0 if g else 0.7, top_k=0 if g else 50,
                seed=i))
            client.send(tl, time.perf_counter())
        while client.outstanding:
            client.step()

    # history buckets: the longest prompts at every padded row count
    # (half the rows plus one pad to the same count, and fit the pool)
    for n in _pow2_upto(slots):
        group([(pmax, i % 2 == 0) for i in range(n // 2 + 1)])
    # first tokens: a row that finishes in the i-th wave after the first
    # has a prompt of i windows and one token
    for waves in first_token_groups(slots):
        group([(C * i + 1, g) for i, (k, g) in enumerate(waves, 1)
               for _ in range(k)])


def build(cell, seed: int, trace: bool):
    """Weights from the seed in one program, then the engine."""
    import jax
    from repro.obs.trace import Tracer
    from repro.serve.engine import ServeEngine
    c = cell.config
    ref = spec.family("reference", c["family"])
    ad = spec.family("adapters", c["family"])
    cfg = ad.model_config(c)
    make = jax.jit(lambda key: ad.program_params(
        c, (w := ref.make_weights(c, key)), ref.serve_scales(w)))
    params = make(ref.seed_key(seed))
    e = dict(c["engine"])
    eng = ServeEngine(cfg, params, trace=Tracer(capacity=1 << 22)
                      if trace else None, **e)
    del params
    return eng, cfg


def open_loop(client: Client, tls: List[Timeline], seconds: float,
              mix: Dict, counter, prof) -> Dict:
    """Send each request at its due time; the window opens after the
    fill and closes ``seconds`` later; then drain until every request due
    in the window has finished (or the drain limit passes). Arrivals go
    on during the drain, so the load the last requests see is the same."""
    t0 = time.perf_counter()
    for tl in tls:
        tl.due = t0 + tl.req.due
    w0 = t0 + float(mix["fill_seconds"])
    w1 = w0 + seconds
    drain = float(mix["drain_seconds"])
    for tl in tls:
        tl.counted = w0 <= tl.due < w1
    i, armed, backlog = 0, False, {}
    while True:
        now = time.perf_counter()
        if not armed and now >= w0:
            counter.armed = armed = True
            backlog["start"] = client.outstanding
            prof.start(now)
        if armed:
            prof.maybe_stop(now)
            if "end" not in backlog and now >= w1:
                backlog["end"] = client.outstanding
        with client.span("bench.send"):
            while i < len(tls) and tls[i].due <= now:
                client.send(tls[i], now)
                i += 1
        counted_left = any(t.counted and not t.done for t in tls)
        if now >= w1 and (not counted_left or now >= w1 + drain):
            break
        if client.outstanding:
            client.step()
        elif i < len(tls):
            with client.span("bench.wait_for_arrival"):
                time.sleep(max(0.0, min(tls[i].due - now, 0.002)))
        else:
            break
    counter.armed = False
    prof.stop()
    return {"w0": w0, "w1": w1, "setup_end": w0, "backlog": backlog}


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, counter, root) -> Dict:
    import jax
    mix = cell.traffic
    c = cell.config
    eng, cfg = build(cell, seed, trace)
    log(f"bench: engine built at {time.perf_counter() - t_start:.1f} s")
    client = Client(eng, cfg.vocab_size)
    warm_up(client, eng.slots, eng.prefill_chunk, mix)
    log(f"bench: warmed up at {time.perf_counter() - t_start:.1f} s")
    client.uid_due.clear()
    tls = [Timeline(req=r)
           for r in tr.requests(mix, seed, seconds, cfg.vocab_size)]
    prof = reduce.Window(root, trace, mix.get("trace_seconds", 1e9),
                         mark=lambda: {"stats": eng.stats(),
                                       "events": len(eng.trace.events())})
    w = open_loop(client, tls, seconds, mix, counter, prof)
    w0, w1 = w["w0"], w["w1"]
    rec: Dict = {"cell": cell.name, "config": c, "traffic": mix,
                 "seconds": seconds, "setup_s": w["setup_end"] - t_start,
                 "window": (w0, w1), "backlog": w["backlog"],
                 "window_compiles": counter.count,
                 "memory_peak_bytes": memory_peak_bytes(cell.chips),
                 "timelines": [t for t in tls if t.sent is not None]}
    rec["e2e"], rec["attempted"], rec["failed"] = client_metrics(
        rec["timelines"], w0, w1)
    rec.update(prof.record())
    if trace:
        m0, m1 = rec.pop("marks")
        rec["counters"] = {"start": m0["stats"], "end": m1["stats"]}
        rec["tracer_events"] = eng.trace.events()[m0["events"]:m1["events"]]
    rec["uid_due"] = client.uid_due
    rec["engine"] = {"slots": eng.slots, "decode_block": eng.decode_block,
                     "prefill_chunk": eng.prefill_chunk}
    log(f"bench: window {w1 - w0:.3f} s, attempted {rec['attempted']}, "
        f"failed {rec['failed']}, backlog {w['backlog']}, e2e {rec['e2e']}")
    # free the program's state before the reference runs
    del eng, client, prof
    gc.collect()
    jax.clear_caches()
    rec["checks"] = correct.serve_checks(cell, seed, rec)
    return rec


def client_metrics(tls: List[Timeline], w0: float, w1: float):
    """End-to-end numbers from what the client saw of the requests due in
    the window."""
    counted = [t for t in tls if t.counted]
    failed = [t for t in counted if not t.done or t.first is None]
    ok = [t for t in counted if t.done and t.first is not None]
    end = max([t.last for t in tls if t.last is not None] + [w1])
    # a request that never answered missed every limit: it counts at the
    # longest wait the run could observe
    ttft = [(t.first - t.due) * 1e3 for t in ok] + \
           [(end - t.due) * 1e3 for t in failed]
    tpot = [(t.last - t.first) * 1e3 / (len(t.tokens) - 1)
            for t in ok if len(t.tokens) > 1]
    lag = [(t.sent - t.due) * 1e3 for t in counted]
    e2e = {"ttft_p95_ms": st.percentile(ttft, 95),
           "ttft_p50_ms": st.percentile(ttft, 50),
           "tpot_p95_ms": st.percentile(tpot, 95),
           "tpot_p50_ms": st.percentile(tpot, 50),
           "send_lag_p95_ms": st.percentile(lag, 95)}
    return e2e, len(counted), len(failed)
