"""Runtime observability: tracer, Perfetto export, /v1/metrics.

Covers: ring bounding + the disabled no-op contract (spans still
measure), span nesting/ordering over a served mixed workload, the
preempt/swap-resume request timeline, Chrome trace_event validity
(b/e pairing, metadata tracks, truncation synthesis), trace-vs-scheduler
latency reconciliation, the stats()-is-JSON regression, Prometheus
rendering consistency with engine.stats(), and an HTTP end-to-end
``GET /v1/metrics`` scrape mid-serve.
"""
import asyncio
import json
import time

import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import init_params
from repro.obs.export import (chrome_trace, compile_split, render_report,
                              request_attribution, step_breakdown)
from repro.obs.metrics import (Histogram, ServeMetrics, parse_prometheus)
from repro.obs.trace import NULL_TRACER, SPAN_NAMES, Tracer
from repro.serve.engine import Request, ServeEngine
from repro.serve.frontend import AsyncFrontend
from repro.serve.http import ServeHTTP
from repro.serve.spec import SpecConfig

from test_frontend import _json_request


def _req(uid, plen, max_new=8, **kw):
    rng = np.random.default_rng(300 + uid)
    return Request(uid=uid, prompt=rng.integers(0, 250, plen)
                   .astype(np.int32), max_new_tokens=max_new, **kw)


@pytest.fixture(scope="module")
def served(rng):
    cfg = get_reduced_config("qwen2.5-3b")
    return cfg, init_params(cfg, rng)


@pytest.fixture(scope="module")
def traced_run(served):
    """One traced mixed run (paged + spec + tight pool -> preemption),
    shared by the timeline/export/report assertions."""
    cfg, params = served
    tracer = Tracer()
    eng = ServeEngine(cfg, params, slots=4, cache_len=64,
                      kv_layout="paged", block_size=8, num_blocks=8,
                      max_seq_len=96, decode_block=4,
                      admission="optimistic", prefix_cache=False,
                      spec=SpecConfig(k=3, draft_layers=1), trace=tracer)
    reqs = [_req(i, 10, max_new=24) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    assert eng.stats()["preemptions"] >= 1, "workload must exercise swap"
    return eng, tracer, reqs


class TestTracer:
    def test_ring_bounds_memory_and_counts_evictions(self):
        tr = Tracer(capacity=8)
        for i in range(100):
            tr.event("submit", uid=i)
        assert len(tr) == 8
        assert tr.dropped == 92
        # oldest evicted, newest kept
        assert [r["uid"] for r in tr.events()] == list(range(92, 100))
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0

    def test_disabled_records_nothing_but_spans_still_measure(self):
        tr = Tracer(enabled=False)
        with tr.span("step") as sp:
            tr.event("submit", uid=0)
            tr.annotate(compiled="decode")
            time.sleep(0.002)
        assert sp.dt >= 0.002          # engine bookkeeping depends on dt
        assert len(tr) == 0 and tr.dropped == 0 and not tr._stack
        assert not NULL_TRACER.enabled and len(NULL_TRACER) == 0

    def test_nesting_depth_and_annotate_target_innermost(self):
        tr = Tracer()
        with tr.span("step"):
            with tr.span("decode", rows=2):
                tr.annotate(compiled="decode")
        spans = {r["name"]: r for r in tr.events()}
        assert spans["decode"]["depth"] == 1      # committed inside step
        assert spans["step"]["depth"] == 0
        assert spans["decode"]["args"] == {"rows": 2, "compiled": "decode"}
        assert spans["step"]["t0"] <= spans["decode"]["t0"]

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)


class TestServedTrace:
    def test_span_vocabulary_nesting_and_step_ordering(self, traced_run):
        """Every span the engine emits is in the documented vocabulary,
        steps are contiguous ascending, and wave spans sit inside their
        step span's window."""
        _, tracer, _ = traced_run
        recs = tracer.events()
        assert tracer.dropped == 0
        spans = [r for r in recs if r["ph"] == "span"]
        assert {s["name"] for s in spans} <= set(SPAN_NAMES)
        # the mixed workload exercised the full machinery
        names = {s["name"] for s in spans}
        assert {"step", "prefill_wave", "spec_draft", "spec_verify",
                "swap_out", "swap_in", "harvest", "sync"} <= names
        steps = {}
        for s in spans:
            if s["name"] == "step":
                steps[s["step"]] = (s["t0"], s["t0"] + s["dur"])
        assert sorted(steps) == list(range(1, len(steps) + 1))
        eps = 1e-4                     # span exit bookkeeping slack
        for s in spans:
            if s["name"] == "step" or s["step"] not in steps:
                continue
            lo, hi = steps[s["step"]]
            assert lo - eps <= s["t0"] <= s["t0"] + s["dur"] <= hi + eps, \
                f"{s['name']} escapes its step window"
            assert s["depth"] >= 1     # committed nested under step

    def test_request_lifecycle_and_swap_timeline(self, traced_run):
        """Each request's events arrive in causal order; the preempted
        request's timeline is submit -> ... -> preempted -> swap_resumed
        -> finished with monotone timestamps."""
        _, tracer, reqs = traced_run
        by_uid = {r.uid: [] for r in reqs}
        for rec in tracer.events():
            if rec["ph"] == "event" and rec.get("uid") in by_uid:
                by_uid[rec["uid"]].append(rec)
        swapped = 0
        for uid, evs in by_uid.items():
            names = [e["name"] for e in evs]
            ts = [e["t"] for e in evs]
            assert ts == sorted(ts)
            assert names[:2] == ["submit", "queued"]
            assert names[-1] == "finished"
            for must in ("admitted", "first_token"):
                assert must in names, f"uid {uid} missing {must}"
            assert names.index("admitted") < names.index("first_token")
            if "preempted" in names:
                swapped += 1
                assert names.index("preempted") \
                    < names.index("swap_resumed") < names.index("finished")
                pre = evs[names.index("preempted")]
                res = evs[names.index("swap_resumed")]
                assert pre["args"]["bytes"] == res["args"]["bytes"] > 0
        assert swapped >= 1

    def test_chrome_export_is_valid_and_pairs_async_spans(self, traced_run):
        eng, tracer, reqs = traced_run
        trace = chrome_trace(tracer, eng.wave_variant_signatures())
        json.loads(json.dumps(trace))            # pure-JSON round trip
        ev = trace["traceEvents"]
        procs = {e["args"]["name"] for e in ev
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert procs == {"engine waves", "requests"}
        tracks = {e["args"]["name"] for e in ev
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "step" in tracks and "spec_verify" in tracks
        for e in ev:
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] >= 0
        # every request opens exactly once and closes exactly once, and
        # no finished request is flagged truncated
        for r in reqs:
            b = [e for e in ev if e["ph"] == "b" and e.get("id") == r.uid]
            e_ = [e for e in ev if e["ph"] == "e" and e.get("id") == r.uid]
            assert len(b) == 1 and len(e_) == 1
            assert "truncated" not in e_[0]["args"]
        assert trace["otherData"]["compile_variants"]

    def test_truncated_request_gets_synthetic_end(self):
        tr = Tracer()
        tr.event("submit", uid=7)
        tr.event("queued", uid=7)
        ends = [e for e in chrome_trace(tr)["traceEvents"]
                if e["ph"] == "e" and e.get("id") == 7]
        assert len(ends) == 1 and ends[0]["args"]["truncated"]

    def test_reconciliation_and_reports(self, traced_run):
        """Trace-side submit->finish deltas agree with the scheduler
        clock within the 5% acceptance bound, and the report functions
        cover every phase of the run."""
        eng, tracer, reqs = traced_run
        trace = chrome_trace(tracer, eng.wave_variant_signatures())
        ra = request_attribution(trace)
        assert ra["finished"] == len(reqs)
        assert ra["reconcile_max_err"] <= 0.05
        assert ra["latency"]["p95_s"] >= ra["ttft"]["p95_s"] > 0
        bd = step_breakdown(trace)
        assert bd["step"]["pct_of_step"] == pytest.approx(100.0)
        assert 0 < bd["spec_verify"]["total_s"] <= bd["step"]["total_s"]
        cs = compile_split(trace)
        # first call of each wave family is compile-tainted
        assert cs["prefill_wave"]["compile_calls"] >= 1
        assert cs["prefill_wave"]["variants"]
        report = render_report(trace)
        for needle in ("step-time breakdown", "request attribution",
                       "compile vs execute", "max rel err"):
            assert needle in report


class TestStatsAndMetrics:
    def test_stats_are_json_clean(self, traced_run):
        """Regression: stats() must serialize with the stock JSON encoder
        (numpy/jax scalars cast at the boundary), and survive a
        round trip unchanged."""
        eng, _, _ = traced_run
        stats = eng.stats()
        assert json.loads(json.dumps(stats)) == stats
        for k, v in stats.items():
            assert not isinstance(v, np.generic), f"{k} leaks {type(v)}"

    def test_histogram_buckets_and_quantiles(self):
        h = Histogram("x_seconds", "t", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        h.observe(None)                # absent observation is dropped
        assert h.count == 5 and h.sum == pytest.approx(5.605)
        parsed = parse_prometheus(h.render())
        assert parsed['x_seconds_bucket{le="0.01"}'] == 1
        assert parsed['x_seconds_bucket{le="1.0"}'] == 4   # cumulative
        assert parsed['x_seconds_bucket{le="+Inf"}'] == 5
        assert h.quantile(50) == 0.1
        assert h.quantile(99) == 1.0   # clamped to the last bound

    def test_parse_prometheus_rejects_malformed(self):
        with pytest.raises(ValueError):
            parse_prometheus("lonely_token\n")
        with pytest.raises(ValueError):
            parse_prometheus("name not_a_number\n")

    def test_render_matches_engine_stats(self, traced_run):
        """The scrape projection agrees with stats() — counter for
        counter — including the spec and swap families this workload
        exercised, and the per-family compile-variant gauges."""
        eng, _, _ = traced_run
        stats = eng.stats()
        parsed = parse_prometheus(eng.metrics.render(stats))
        for key, name in (("tokens_out", "serve_tokens_out_total"),
                          ("preemptions", "serve_preemptions_total"),
                          ("spec_waves", "serve_spec_waves_total"),
                          ("requests_finished",
                           "serve_requests_finished_total"),
                          ("free_blocks", "serve_free_blocks")):
            assert parsed[name] == pytest.approx(stats[key]), name
        for fam, n in stats["compile_variants"].items():
            assert parsed[f'serve_compile_variants{{family="{fam}"}}'] == n
        assert parsed["serve_request_latency_seconds_count"] == \
            stats["requests_finished"]

    def test_observe_finished_derives_tpot(self):
        m = ServeMetrics()
        m.observe_ttft(0.02)
        m.observe_finished(0.5, 0.4, 9)          # 0.4 s over 8 tokens
        snap = m.snapshot()
        assert snap["ttft"]["count"] == snap["latency"]["count"] == 1
        assert snap["tpot"]["count"] == 1
        assert m.tpot.sum == pytest.approx(0.05)
        m.observe_finished(0.5, 0.4, 1)          # single token: no TPOT
        assert m.snapshot()["tpot"]["count"] == 1
        m.reset()
        assert m.snapshot()["latency"]["count"] == 0


async def _text_request(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(b"GET %s HTTP/1.1\r\n\r\n" % path.encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    header, _, payload = raw.partition(b"\r\n\r\n")
    lines = header.decode().split("\r\n")
    headers = dict((k.strip().lower(), v.strip()) for k, _, v in
                   (ln.partition(":") for ln in lines[1:]))
    return int(lines[0].split()[1]), headers, payload.decode()


class TestHTTPMetrics:
    def test_scrape_mid_serve_and_after_drain(self, served):
        """GET /v1/metrics parses as Prometheus text both while requests
        are in flight and after the drain, when its counters must agree
        with the frontend stats snapshot."""
        cfg, params = served
        eng = ServeEngine(cfg, params, slots=2, cache_len=64,
                          kv_layout="paged", block_size=16, num_blocks=16,
                          max_seq_len=64, decode_block=4, trace=Tracer())

        async def run():
            async with AsyncFrontend(eng) as fe:
                async with ServeHTTP(fe, port=0) as srv:
                    handles = [await fe.submit([9 + i] * 8,
                                               max_new_tokens=12)
                               for i in range(4)]
                    mid = await _text_request(srv.port, "/v1/metrics")
                    for h in handles:
                        await h.tokens()
                    done = await _text_request(srv.port, "/v1/metrics")
                    code, stats = await _json_request(srv.port, "GET",
                                                      "/v1/stats")
            return mid, done, code, stats

        mid, done, code, stats = asyncio.run(run())
        assert mid[0] == done[0] == code == 200
        assert done[1]["content-type"].startswith(
            "text/plain; version=0.0.4")
        assert parse_prometheus(mid[2])          # well-formed mid-flight
        parsed = parse_prometheus(done[2])
        assert parsed["serve_requests_finished_total"] == 4
        assert parsed["serve_tokens_out_total"] == \
            stats["tokens_out"] == 4 * 12
        assert parsed["serve_ttft_seconds_count"] == 4
        # /v1/stats carries the matching histogram digest
        assert stats["metrics"]["ttft"]["count"] == 4
        assert json.loads(json.dumps(stats)) == stats


class TestProfilerClock:
    """The tracer on the profiler's clock: an enabled span is also a
    ``repro.<name>`` profiler annotation, a disabled one is not."""

    def test_disabled_tracer_opens_no_annotation_and_imports_no_jax(self):
        import os
        import subprocess
        import sys
        code = ("import sys\n"
                "import repro.obs.trace as t\n"
                "tr = t.Tracer(enabled=False)\n"
                "with tr.span('decode_chunk'):\n"
                "    pass\n"
                "with t.NULL_TRACER.span('harvest'):\n"
                "    pass\n"
                "assert 'jax' not in sys.modules\n"
                "assert t._annotation_cls is None and len(tr) == 0\n")
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
        subprocess.run([sys.executable, "-c", code], check=True, env=env)

    def test_disabled_span_never_builds_an_annotation(self, monkeypatch):
        import repro.obs.trace as trace_mod

        def boom(name):
            raise AssertionError(f"annotation {name} opened")

        monkeypatch.setattr(trace_mod, "_annotation", boom)
        with Tracer(enabled=False).span("decode_chunk"):
            pass

    def test_enabled_span_is_a_profiler_annotation_on_the_device_clock(
            self, tmp_path):
        """``repro.<name>`` lands on the profiler's host plane, and the
        operations run inside the span fall inside it."""
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda x: jnp.tanh(x @ x) * 2.0)
        x = jnp.ones((64, 64))
        f(x).block_until_ready()
        tr = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        with tr.span("decode_chunk"):
            f(x).block_until_ready()
        jax.profiler.stop_trace()
        spans, ops = [], []
        for e in _profiled_events(tmp_path):
            if e.name == "repro.decode_chunk":
                spans.append((e.start_ns, e.end_ns))
            elif dict(e.stats).get("hlo_module") == "jit__lambda":
                ops.append((e.start_ns, e.end_ns))
        assert len(spans) == 1 and ops
        (s, e), = spans
        assert all(s <= a and b <= e for a, b in ops)
        assert [r["name"] for r in tr.events()] == ["decode_chunk"]

    def test_nested_annotations_close_when_the_span_body_raises(
            self, tmp_path):
        import jax
        tr = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        with tr.span("step"):
            with pytest.raises(RuntimeError):
                with tr.span("sync"):
                    raise RuntimeError("device lost")
        with tr.span("harvest"):
            pass
        jax.profiler.stop_trace()
        got = {e.name: (e.start_ns, e.end_ns) for e in
               _profiled_events(tmp_path) if e.name.startswith("repro.")}
        assert set(got) == {"repro.step", "repro.sync", "repro.harvest"}
        (s0, e0), (s1, e1) = got["repro.step"], got["repro.sync"]
        assert s0 <= s1 and e1 <= e0
        assert got["repro.harvest"][0] >= e0
        assert [(r["name"], r["depth"]) for r in tr.events()] == [
            ("sync", 1), ("step", 0), ("harvest", 0)]

    def test_every_engine_span_reaches_the_profiler_trace(
            self, served, tmp_path):
        """The serve engine's spans need nothing of their own to be on
        the profiler's clock: each recorded span is one ``repro.*``
        annotation."""
        import jax
        cfg, params = served
        tr = Tracer()
        eng = ServeEngine(cfg, params, slots=2, cache_len=32,
                          decode_block=4, trace=tr)
        eng.submit(_req(0, 6, max_new=6))
        jax.profiler.start_trace(str(tmp_path))
        eng.run_until_drained()
        jax.profiler.stop_trace()
        spans = [r["name"] for r in tr.events() if r["ph"] == "span"]
        assert {"step", "decode_chunk", "sync"} <= set(spans)
        annotations = [e.name[len("repro."):] for e in
                       _profiled_events(tmp_path)
                       if e.name.startswith("repro.")]
        assert sorted(annotations) == sorted(spans)


def _profiled_events(trace_dir):
    """Every event of the one profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [e for plane in pd.planes for line in plane.lines
            for e in line.events]
