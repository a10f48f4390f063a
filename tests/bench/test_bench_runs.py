"""Whole runs of the cells on the CPU at a small size, with the harness's
look for a chip skipped: a sound run comes out correct; the control (the
reference at the next precision down, or the program's own lower
precision path) and each fault the cell can have come out not correct."""
from __future__ import annotations

import io
import json
import time
from pathlib import Path

import pytest

from bench.lib import correct, harness, spec
import bench_tiny as tiny

REPO = Path(__file__).resolve().parents[2]
SEED = 5_000_000_123          # wider than 32 bits


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")


@pytest.fixture
def chat(tmp_path):
    """A root whose BENCHMARK.json holds the chat cell."""
    return tiny.chat_root(tmp_path, REPO)


def run_cell(workload, shrink, seconds=1.5, root=REPO):
    out = io.StringIO()
    rc = harness.run(["--workload", workload, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", "0"],
                     root=root, t_start=time.perf_counter(),
                     require_chip=False, override=shrink, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def serve_tiny(cell):
    tiny.serve(cell)
    cell.config["engine"]["slots"] = 2


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def test_chat_run_is_correct_and_reports_its_metrics(chat):
    r = run_cell("qwen2.5-3b.chat", serve_tiny, root=chat)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


def test_chat_control_at_lower_precision_fails(monkeypatch, chat):
    """The reference at the configuration's control precision, put in the
    program's place through the run's own comparison, is not correct."""
    from bench.lib import serve
    seen = {}
    real = correct.serve_checks

    def with_control(cell, seed, rec):
        out = real(cell, seed, rec, cell.config["check"]["control"])
        seen.update(rec["readings"], limit=cell.config["check"]["limit"])
        return out

    monkeypatch.setattr(serve.correct, "serve_checks", with_control)
    r = run_cell("qwen2.5-3b.chat", serve_tiny, root=chat)
    assert r["correct"] is False
    assert r["checks"]["served_logit_gap"]["value"] == seen["control"]
    assert seen["control"] > seen["limit"] > seen["served"]


def test_chat_token_altered_where_produced_is_not_correct(monkeypatch,
                                                          chat):
    import repro.serve.engine as engine
    real = engine.sample_tokens

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_tokens", altered)
    assert run_cell("qwen2.5-3b.chat", serve_tiny,
                    root=chat)["correct"] is False


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def test_qat_run_is_correct_and_reports_its_metrics():
    r = run_cell("qwen2.5-3b-qat4.kd", tiny.train)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert r["attempted"] > 0


def _patched_step(monkeypatch, wrap):
    import repro.launch.steps as steps
    real = steps.make_train_step

    def make(cfg, tcfg, *a, **k):
        return wrap(real(cfg, tcfg, *a, **k))

    monkeypatch.setattr(steps, "make_train_step", make)


def test_qat_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    def wrap(step):
        def unchanged(params, teacher, opt, batch, i):
            _, _, metrics = step(params, teacher, opt, batch, i)
            return params, opt, metrics
        return unchanged
    _patched_step(monkeypatch, wrap)
    assert run_cell("qwen2.5-3b-qat4.kd", tiny.train)["correct"] is False


def test_qat_half_the_batch_left_out_is_not_correct(monkeypatch):
    def wrap(step):
        def half(params, teacher, opt, batch, i):
            S = batch["loss_mask"].shape[-1]
            keep = (batch["loss_mask"].at[..., S // 2:].set(0.0))
            return step(params, teacher, opt, {**batch, "loss_mask": keep},
                        i)
        return half
    _patched_step(monkeypatch, wrap)
    assert run_cell("qwen2.5-3b-qat4.kd", tiny.train)["correct"] is False


def test_qat_control_at_lower_precision_fails():
    def lower(cell):
        tiny.train(cell)
        cell.config["train"]["precision"] = "A4d-C4-W4"
    assert run_cell("qwen2.5-3b-qat4.kd", lower)["correct"] is False


def test_run_without_a_chip_prints_nothing_and_fails():
    out = io.StringIO()
    rc = harness.run(["--workload", "qwen2.5-3b-qat4.kd", "--seed", "1",
                      "--seconds", "1", "--trace", "0"], root=REPO,
                     t_start=time.perf_counter(), out=out)
    assert rc != 0 and out.getvalue() == ""


def test_cells_resolve(chat):
    assert spec.cell("qwen2.5-3b-qat4.kd", REPO).chips == 1
    assert spec.cell("qwen2.5-3b.chat", chat).chips == 1
