"""The benchmark's own arithmetic on a fake clock and on hand-worked
numbers: percentiles, traffic, client metrics, trace reduction, peaks,
operation and byte counts. Nothing here needs an accelerator."""
from __future__ import annotations

import json
import statistics

import numpy as np
import pytest

from bench.lib import costs, peaks, readers, stats, trace, traffic
from bench.lib.serve import Timeline, client_metrics


# --------------------------------------------------------------------------
# percentiles
# --------------------------------------------------------------------------

def test_percentile_interpolates_between_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0


def test_percentile_of_empty_sample_fails():
    with pytest.raises(stats.EmptySample):
        stats.percentile([], 95)


def test_spread_is_iqr_over_median():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

MIX = {"kind": "open_loop", "rate_per_s": 4.0,
       "prompt": {"dist": "lognormal", "median": 100, "sigma": 0.8,
                  "min": 20, "max": 400},
       "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                  "min": 4, "max": 64},
       "greedy_share": 0.5, "sampled": {"temperature": 0.7, "top_k": 50},
       "fill_seconds": 2, "drain_seconds": 3}


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.requests(MIX, 5_000_000_001, 10, vocab=1000)
    b = traffic.requests(MIX, 7, 10, vocab=1000)
    assert len(a) == len(b) == 4 * 2 + 4 * 10 + 4 * 3
    for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.temperature):
        assert sorted(map(key, a)) == sorted(map(key, b))
    gaps = lambda rs: sorted(np.round(np.diff([r.due for r in rs]), 9))
    assert sorted(np.diff([r.due for r in a]))[-1] > 0
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    again = traffic.requests(MIX, 5_000_000_001, 10, vocab=1000)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    assert gaps(a) != [] and a[0].due == 0.0


def test_the_window_gets_the_same_requests_at_the_mean_rate():
    for seed in (1, 2, 5_000_000_001):
        rs = traffic.requests(MIX, seed, 10, vocab=1000)
        inside = [r for r in rs if 2.0 <= r.due < 12.0]
        assert len(inside) in (40, 41)
        window = rs[8:48]
        assert window[0].due == pytest.approx(2.0)
        assert sorted(len(r.prompt) for r in window) == sorted(
            traffic.quantiles(MIX["prompt"], 40))


def test_lengths_respect_clip_and_greedy_share():
    rs = traffic.requests(MIX, 3, 10, vocab=1000)
    assert all(20 <= len(r.prompt) <= 400 for r in rs)
    assert all(4 <= r.max_new <= 64 for r in rs)
    greedy = sum(r.temperature <= 0 for r in rs)
    assert greedy == round(len(rs) * 0.5)
    assert all(r.top_k == 50 for r in rs if r.temperature > 0)


# --------------------------------------------------------------------------
# client metrics on a fake clock
# --------------------------------------------------------------------------

def _tl(due, sent, chunks, counted=True, done=True):
    t = Timeline(req=traffic.Req(index=0, prompt=np.zeros(4, np.int32),
                                 max_new=8, temperature=0.0, top_k=0,
                                 seed=0))
    t.due, t.sent, t.counted, t.done = due, sent, counted, done
    for ts, n in chunks:
        t.first = ts if t.first is None else t.first
        t.last = ts
        t.chunks.append((ts, n))
        t.tokens.extend([1] * n)
    return t


def test_ttft_runs_from_the_due_time_not_the_send():
    # due at 1.0, sent late at 1.5 (the loop stalled), first token at 2.0
    tls = [_tl(1.0, 1.5, [(2.0, 1), (3.0, 4)])]
    e2e, attempted, failed = client_metrics(tls, 0.0, 10.0)
    assert e2e["ttft_p95_ms"] == pytest.approx(1000.0)
    assert e2e["send_lag_p95_ms"] == pytest.approx(500.0)
    assert e2e["tpot_p95_ms"] == pytest.approx(1000.0 / 4)
    assert (attempted, failed) == (1, 0)


def test_tokens_cut_at_the_window_edges():
    # only the tokens delivered inside [1.0, 2.0) count, each at its
    # context length; the first token of a request comes from its prefill
    tl = _tl(0.5, 0.5, [(0.9, 3), (1.1, 2), (1.9, 4), (2.0, 5)])
    rec = {"timelines": [tl], "trace_host": (1.0, 2.0)}
    assert readers.decode_contexts(rec) == [4 + j for j in range(3, 9)]
    first = _tl(0.5, 0.5, [(1.5, 1), (1.6, 2), (2.5, 1)])
    assert readers.decode_contexts({"timelines": [first],
                                    "trace_host": (1.0, 2.0)}) == [5, 6]


def test_unanswered_request_fails_and_counts_as_a_miss():
    tls = [_tl(1.0, 1.0, [(1.2, 2), (1.4, 2)]),
           _tl(1.5, 1.5, [], done=False)]
    e2e, attempted, failed = client_metrics(tls, 0.0, 3.0)
    assert (attempted, failed) == (2, 1)
    # the missing request counts at the longest wait the run saw
    assert e2e["ttft_p95_ms"] >= (3.0 - 1.5) * 1e3 * 0.9


def test_no_counted_request_is_no_result():
    tls = [_tl(1.0, 1.0, [(1.2, 2)], counted=False)]
    with pytest.raises(stats.EmptySample):
        client_metrics(tls, 0.0, 3.0)


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------

def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45)]
    assert trace.union_ns(iv) == 35
    assert trace.gaps(iv, 0, 50) == [(20, 30), (45, 50)]
    assert trace.union_ns([]) == 0


def test_reduce_planes_busy_programs_ops_and_gap_owners():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit__decode_chunk(1)", 100, 300),
                            ("jit__tail_wave(2)", 500, 200)],
            "XLA Ops": [("%while.1 = (f32[]) while(f32[] %x)", 100, 300),
                        ("fusion.1", 100, 100), ("_kernel", 150, 250),
                        ("fusion.2", 500, 200)]},
        "/host:CPU": {"python": [("bench.engine_step", 0, 800),
                                 ("bench.send", 420, 60)]},
    }
    r = trace.reduce_planes(planes)
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["window_s"] == pytest.approx(800e-9)
    assert r["programs"]["jit__decode_chunk"] == pytest.approx(300e-9)
    assert r["calls"]["jit__tail_wave"] == 1
    assert r["ops"]["_kernel"] == pytest.approx(250e-9)
    assert r["by_program"]["jit__decode_chunk"]["_kernel"] == \
        pytest.approx(250e-9)
    assert r["by_program"]["jit__tail_wave"]["fusion.2"] == \
        pytest.approx(200e-9)
    gaps = dict((n, s) for n, s in r["breakdown"]["idle_gaps"])
    assert gaps["bench.send"] == pytest.approx(100e-9)      # 400..500
    assert len(r["breakdown"]["device_ops"]) == 3


def test_reduce_planes_needs_a_device():
    with pytest.raises(ValueError):
        trace.reduce_planes({"/host:CPU": {"python": []}})


def test_recorded_cpu_trace_loads_with_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            f(x).block_until_ready()
    files = list(tmp_path.glob("**/*.xplane.pb"))
    assert files
    planes = trace.load(files[0])
    names = {n for lines in planes.values() for evs in lines.values()
             for (n, _, _) in evs}
    assert "bench.engine_step" in names
    # a CPU trace has no device plane: the reduction refuses it
    with pytest.raises(ValueError):
        trace.reduce_planes(planes)


# --------------------------------------------------------------------------
# peaks and counts
# --------------------------------------------------------------------------

def test_peaks_by_device_kind_and_unknown_kind_is_an_error():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


QWEN25_3B = {"hidden_size": 2048, "num_attention_heads": 16,
             "num_key_value_heads": 2, "intermediate_size": 11008,
             "num_hidden_layers": 36, "vocab_size": 151936}


def test_hand_worked_counts():
    # one 2x4x6 matmul with bias: 96 ops; 6*4/2 + 24 + 24 + 8 + 8 + 24 B
    assert costs.w4a8_call(2, 4, 6, True) == (96, 12 + 24 + 24 + 8 + 8 + 24)
    layer = (2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048 + 3 * 2048 * 11008)
    assert costs.matmul_params(QWEN25_3B) == 36 * layer + 2048 * 151936
    # K/V int8 plus f32 scales: 36 layers x 2 x 2 heads x (128 + 4) B
    assert costs.kv_bytes_per_token(QWEN25_3B) == 19008
    packed = costs.packed_weight_bytes(QWEN25_3B)
    assert 1.39e9 + 0.15e9 < packed < 1.39e9 + 0.17e9 + 0.02e9
    t, bound = costs.least_time(2e12, 1e9, 1e12, 1e12)
    assert (t, bound) == (2.0, "compute")


def test_config_files_are_json_objects():
    from bench.lib.spec import BENCH_DIR
    for p in (BENCH_DIR / "configs").glob("*.json"):
        c = json.loads(p.read_text())
        assert isinstance(c["reduced"], list) and c["family"]


# --------------------------------------------------------------------------
# warm-up plan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("slots", [1, 2, 3, 5, 8, 16])
def test_first_token_groups_cover_every_pair_and_flag(slots):
    from bench.lib.serve import _pow2_upto, first_token_groups
    pad = lambda n: min(1 << (n - 1).bit_length(), slots)   # noqa: E731
    pairs, flags = set(), set()
    for waves in first_token_groups(slots):
        n = sum(k for k, _ in waves)
        assert 1 <= n <= slots
        for k, g in waves:
            pairs.add((pad(n), k))
            flags.add((k, g))
            n -= k
    assert pairs >= {(p, k) for p in _pow2_upto(slots)
                     for k in range(1, p + 1)}
    assert flags >= {(k, g) for k in range(1, slots + 1)
                     for g in (True, False)}
