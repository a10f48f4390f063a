"""Whole runs of ``granite-4.0-h-micro-qat10.kd`` on the CPU at a small
size, with the harness's look for a chip skipped: a sound run is correct
and reports the cell's metrics; an unchanged state, half of each sequence
left out of the loss, and the A4d-C4-W4 control are not correct. And the
``mamba`` and ``ssd`` scopes its readers match: in the compiled step,
and reduced from a trace."""
from __future__ import annotations

import io
import json
import time
from pathlib import Path

import pytest

from bench.lib import harness, phases, scopes, spec, trace

REPO = Path(__file__).resolve().parents[2]
SEED = 5_000_000_123          # wider than 32 bits
CELL = "granite-4.0-h-micro-qat10.kd"

# Mamba and attention widths cut, the period of 10 layers kept; 32 tokens
# run 4 SSD chunks of 8
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "shared_intermediate_size": 128, "vocab_size": 256,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_chunk_size": 8}


def tiny(cell) -> None:
    cell.config.update(TINY)
    # at this size sound runs read loss gaps under 1e-6, worst-leaf
    # gradient gaps near 0.05-0.08 and change gaps near 0.06-0.10; the
    # control (A4d-C4-W4) 1.4e-5 / 1.15 / 0.25, half of each sequence
    # left out 4e-6 / 1.65 / 0.18, an unchanged state 1.0 on both norms
    cell.config["check"].update(loss_rel=3e-6, grad_norm=0.3,
                                change_norm=0.15)
    cell.traffic.update(seq_len=32)
    cell.traffic["trace_seconds"] = 1.0


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")


def run_cell(shrink, seconds=1.5):
    out = io.StringIO()
    rc = harness.run(["--workload", CELL, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", "0"],
                     root=REPO, t_start=time.perf_counter(),
                     require_chip=False, override=shrink, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _patched_step(monkeypatch, wrap):
    import repro.launch.steps as steps
    real = steps.make_train_step

    def make(cfg, tcfg, *a, **k):
        return wrap(real(cfg, tcfg, *a, **k))

    monkeypatch.setattr(steps, "make_train_step", make)


def test_granite_run_is_correct_and_reports_its_metrics():
    r = run_cell(tiny)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0


def test_granite_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    def wrap(step):
        def unchanged(params, teacher, opt, batch, i):
            _, _, metrics = step(params, teacher, opt, batch, i)
            return params, opt, metrics
        return unchanged
    _patched_step(monkeypatch, wrap)
    assert run_cell(tiny)["correct"] is False


def test_granite_half_the_batch_left_out_is_not_correct(monkeypatch):
    def wrap(step):
        def half(params, teacher, opt, batch, i):
            S = batch["loss_mask"].shape[-1]
            keep = batch["loss_mask"].at[..., S // 2:].set(0.0)
            return step(params, teacher, opt, {**batch, "loss_mask": keep},
                        i)
        return half
    _patched_step(monkeypatch, wrap)
    assert run_cell(tiny)["correct"] is False


def test_granite_control_at_lower_precision_fails():
    def lower(cell):
        tiny(cell)
        cell.config["train"]["precision"] = "A4d-C4-W4"
    assert run_cell(lower)["correct"] is False


# --------------------------------------------------------------------------
# the scopes of qat_mamba_ms and qat_ssd_ms
# --------------------------------------------------------------------------

def test_mamba_and_ssd_scopes_reach_the_compiled_step():
    """Forward and backward, ``ssd`` always inside ``mamba`` and both
    inside ``layers``, so the scope readers read parts of
    ``qat_layers_ms``."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    cell = spec.cell(CELL, REPO)
    tiny(cell)
    c, mix = cell.config, cell.traffic
    ad = spec.family("adapters", c["family"])
    ref = spec.family("reference", c["family"])
    tcfg = TrainConfig(**c["train"], batch_size=1, seq_len=mix["seq_len"])
    params = jax.eval_shape(lambda k: ad.program_params(
        c, ref.make_weights(c, k)), ref.seed_key(1))
    tok = jax.ShapeDtypeStruct((1, mix["seq_len"]), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "loss_mask": jax.ShapeDtypeStruct(tok.shape, jnp.float32)}
    hlo = jax.jit(make_train_step(ad.model_config(c), tcfg)).lower(
        params, params, jax.eval_shape(adamw_init, params), batch,
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    # whole paths (instructions of a reduction's own computation carry a
    # partial one, such as "mamba/reduce_sum")
    names = re.findall(r'op_name="(jit\(train_step\)/[^"]*)"', hlo)
    for scope in scopes.SCOPES:
        under = [n for n in names if scope in phases.scopes(n)]
        assert any("transpose(" not in n for n in under), "no forward op"
        assert any("transpose(" in n for n in under), "no backward op"
        assert all({"layers", "mamba"} <= set(phases.scopes(n))
                   for n in under)


PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_train_step(11)", 0, 1000),
                        ("jit_other(12)", 2000, 500)],
        "XLA Ops": [("%fusion.1 = bf16[8] fusion(x)", 0, 300),
                    ("%while.2 = (f32[]) while(f32[] %x)", 300, 250),
                    ("%fusion.3 = f32[8] fusion(y)", 300, 200),
                    ("%fusion.4 = f32[8] fusion(z)", 600, 100),
                    ("%fusion.5 = f32[8] fusion(w)", 750, 150),
                    ("%fusion.1 = f32[4] fusion(v)", 2000, 400)]},
}
OP_NAMES = {
    "jit_train_step(11)": {
        "fusion.1": "jit(train_step)/jvp(layers)/while/body/mamba/dot",
        "while.2": "jit(train_step)/jvp(layers)/while/body/mamba/ssd/while",
        "fusion.3": "jit(train_step)/transpose(jvp(layers))/while/body/"
                    "mamba/ssd/exp",
        "fusion.4": "jit(train_step)/jvp(layers)/while/body/mlp/dot",
        "fusion.5": "jit(train_step)/optimizer/mul"},
    "jit_other(12)": {"fusion.1": "jit(other)/mamba/add"},
}


@pytest.fixture
def trace_file(tmp_path, monkeypatch):
    """The trace file the readers find, standing for ``PLANES``; its HLO
    protos give ``OP_NAMES``, or what the test puts in ``names``."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(phases, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(trace, "load", lambda path: PLANES)
    names = dict(OP_NAMES)
    monkeypatch.setattr(scopes.xspace, "op_names", lambda path: names)
    return names


def test_scope_readers_read_the_steps_operations_under_each_scope(
        trace_file):
    """A loop counts in neither; ``ssd`` counts inside ``mamba`` too."""
    rec = {"trace": trace.reduce_planes(PLANES)}
    assert spec.reader("qat_mamba_ms").read(rec) == pytest.approx(500e-6)
    assert spec.reader("qat_ssd_ms").read(rec) == pytest.approx(200e-6)


def test_scope_readers_read_nothing_without_the_scopes(trace_file):
    """The program before the mixer existed, and another run's trace."""
    for module, ops in OP_NAMES.items():
        trace_file[module] = {i: "jit(train_step)/layers/mul" for i in ops}
    rec = {"trace": trace.reduce_planes(PLANES)}
    other = {"trace": {"programs": {"jit_train_step": 2e-6}}}
    for name in ("qat_mamba_ms", "qat_ssd_ms"):
        assert spec.reader(name).read(dict(rec)) is None
        assert spec.reader(name).read(other) is None


def test_hybrid_mfu_counts_the_published_period():
    """Per token at 1024 tokens: 4 forwards of 2 x 951.7 M matmul weights
    (9 Mamba-2 layers of 76.15 M, one attention layer of 60.82 M, the
    102.76 M half head), attention and 9 SSDs of 4.26 MFLOP."""
    from bench.lib import costs_hybrid
    c = spec.cell(CELL, REPO).config
    assert costs_hybrid.matmul_params(c) == \
        9 * 76_152_832 + 60_817_408 + 2048 * 50_176
    assert costs_hybrid.ssd_flops_per_token(c, 1024) == 4_259_840
    rec = {"config": c, "traffic": {"seq_len": 1024},
           "device": {"kind": "TPU v5 lite"}, "e2e": {"train_tok_s": 1e4}}
    mfu = spec.reader("qat_mfu.hybrid").read(rec)
    assert mfu == pytest.approx(100 * costs_hybrid.qat_flops_per_token(
        c, 1024) * 1e4 / 197e12)
