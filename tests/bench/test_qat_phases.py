"""The QAT step's phases: the named scopes the readers of ``qat_*_ms``
match are the ones the compiled step carries, forward and backward; the
scopes leave the compiled program as it was; and the reduction of a
trace to device time per phase, on hand-built planes and on the HLO
protos of a recorded CPU trace."""
from __future__ import annotations

import contextlib
import re
from pathlib import Path

import pytest

import bench_tiny as tiny
from bench.lib import phases, spec, trace, xspace

REPO = Path(__file__).resolve().parents[2]
READERS = {"qat_layers_ms": "layers", "qat_vocab_ms": "vocab",
           "qat_optimizer_ms": "optimizer",
           "qat_unscoped_ms": phases.UNSCOPED}


def _compile_step():
    """The cell's compiled QAT step at the tiny size of the benchmark's
    CPU runs."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    cell = spec.cell("qwen2.5-3b-qat4.kd", REPO)
    tiny.train(cell)
    c, mix = cell.config, cell.traffic
    ad = spec.family("adapters", c["family"])
    ref = spec.family("reference", c["family"])
    cfg = ad.model_config(c)
    tcfg = TrainConfig(**c["train"], batch_size=mix["batch_size"],
                       seq_len=mix["seq_len"], dclm_ratio=mix["dclm_ratio"])
    params = jax.eval_shape(lambda k: ad.program_params(
        c, ref.make_weights(c, k)), ref.seed_key(1))
    opt = jax.eval_shape(adamw_init, params)
    B, S = mix["batch_size"], mix["seq_len"]
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 2))
    return step.lower(params, params, opt, batch,
                      jax.ShapeDtypeStruct((), jnp.int32)).compile()


@pytest.fixture(scope="module")
def step_hlo():
    return _compile_step().as_text()


def _strip_metadata(hlo: str) -> str:
    """The HLO text without what only describes where ops came from: each
    instruction's ``metadata`` and the module's stack-frame tables."""
    hlo = re.sub(r"\nFileNames\n.*?\n(?=%|ENTRY)", "\n", hlo, flags=re.S)
    return re.sub(r", metadata=\{[^}]*\}", "", hlo)


def test_every_phase_scope_reaches_the_compiled_step(step_hlo):
    names = re.findall(r'op_name="([^"]*)"', step_hlo)
    fwd = {s for n in names if "transpose(" not in n
           for s in phases.scopes(n)}
    bwd = {s for n in names if "transpose(" in n for s in phases.scopes(n)}
    for scope in phases.SCOPE_PHASE:
        assert scope in fwd, f"no forward op under {scope!r}"
    # the student's scopes and the loss are differentiated
    for scope in ("embed", "layers", "head", "kd_loss"):
        assert scope in bwd, f"no backward op under {scope!r}"
    assert {phases.phase(n) for n in names} == set(phases.PHASES) | {
        phases.UNSCOPED}


def test_scopes_leave_the_compiled_step_unchanged(step_hlo, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compile_step().as_text()
    assert "/layers/" in step_hlo and "/layers/" not in plain
    assert _strip_metadata(plain) == _strip_metadata(step_hlo)


def test_readers_name_the_phases_of_the_table():
    for name, phase in READERS.items():
        assert spec.reader(name).PHASE == phase


def test_phase_of_an_op_name_is_its_innermost_phase_scope():
    assert phases.scopes("jit(train_step)/transpose(jvp(layers))/while"
                         "/body/dot_general") == [
        "train_step", "layers", "while", "body", "dot_general"]
    assert phases.phase("jit(train_step)/layers/while/x") == "layers"
    assert phases.phase("jit(train_step)/transpose(jvp(kd_loss))/"
                        "jit(log_softmax)/exp") == "vocab"
    assert phases.phase("jit(train_step)/jvp(embed)/jit(_take)") == "vocab"
    assert phases.phase("jit(train_step)/optimizer/jit(clip)/max") == \
        "optimizer"
    assert phases.phase("jit(train_step)/head/jit(clip)/layers/x") == \
        "layers"
    assert phases.phase("jit(train_step)/cos") == phases.UNSCOPED
    assert phases.phase("") == phases.UNSCOPED


# a step of 1,000 ns with a loop, three scoped operations, one without
# metadata and 200 ns between operations; and another program
PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_train_step(11)", 0, 1000),
                        ("jit_other(12)", 2000, 500)],
        "XLA Ops": [("%fusion.1 = bf16[8] fusion(x)", 0, 300),
                    ("%while.2 = (f32[]) while(f32[] %x)", 300, 250),
                    ("%fusion.3 = f32[8] fusion(y)", 300, 200),
                    ("%copy.4 = f32[8] copy(z)", 600, 100),
                    ("%fusion.5 = f32[8] fusion(w)", 750, 150),
                    ("%fusion.1 = f32[4] fusion(v)", 2000, 400)]},
    "/host:CPU": {"python": [("bench.dispatch_step", 0, 2600)]},
}
OP_NAMES = {
    "jit_train_step(11)": {
        "fusion.1": "jit(train_step)/jvp(layers)/while/dot_general",
        "while.2": "jit(train_step)/jvp(layers)/while",
        "fusion.3": "jit(train_step)/transpose(jvp(head))/dot",
        "copy.4": "",
        "fusion.5": "jit(train_step)/optimizer/mul"},
    "jit_other(12)": {"fusion.1": "jit(other)/layers/add"},
}


def test_reduce_ops_by_phase():
    r = phases.reduce_ops(PLANES, OP_NAMES)
    assert r["by_phase"]["jit_train_step"] == pytest.approx({
        "layers": 300e-9, "vocab": 200e-9, "optimizer": 150e-9,
        phases.UNSCOPED: 100e-9})
    # the same instruction name in another program has its own op_name
    assert r["by_phase"]["jit_other"] == pytest.approx({"layers": 400e-9})
    assert r["calls"] == {"jit_train_step": 1, "jit_other": 1}
    ms = phases.step_ms(r)
    assert ms == pytest.approx({"layers": 300e-6, "vocab": 200e-6,
                                "optimizer": 150e-6,
                                phases.UNSCOPED: 350e-6})


def test_a_fusion_counts_whole_to_the_phase_of_its_own_op_name():
    """Instructions fused from other scopes (a cast of the optimizer's
    inside a head fusion) move none of the fusion's time."""
    names = {m: dict(ops) for m, ops in OP_NAMES.items()}
    names["jit_train_step(11)"].update({
        "convert.7": "jit(train_step)/optimizer/convert_element_type",
        "add.8": "jit(train_step)/jvp(layers)/while/add"})
    assert phases.reduce_ops(PLANES, names) == \
        phases.reduce_ops(PLANES, OP_NAMES)


@pytest.fixture
def trace_file(tmp_path, monkeypatch):
    """The trace file the readers find, standing for ``PLANES``; its HLO
    protos give ``OP_NAMES``, or what the test puts in ``names``."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(phases, "TRACE_DIR", tmp_path)
    names = dict(OP_NAMES)
    monkeypatch.setattr(phases, "reduce_file",
                        lambda path: phases.reduce_ops(PLANES, names))
    return names


def _rec():
    return {"trace": trace.reduce_planes(PLANES)}


def test_four_readers_add_up_to_train_step_ms(trace_file):
    rec = _rec()
    got = {n: spec.reader(n).read(rec) for n in READERS}
    assert got == pytest.approx({
        "qat_layers_ms": 300e-6, "qat_vocab_ms": 200e-6,
        "qat_optimizer_ms": 150e-6, "qat_unscoped_ms": 350e-6})
    step = spec.reader("train_step_ms").read(rec)
    assert sum(got.values()) == pytest.approx(step, rel=1e-12)


def test_readers_read_nothing_from_another_trace(trace_file):
    other = {"trace": {"programs": {"jit_train_step": 2e-6}}}
    assert all(spec.reader(n).read(other) is None for n in READERS)
    assert all(spec.reader(n).read({"trace": {}}) is None for n in READERS)


def test_readers_read_nothing_from_a_step_without_scopes(trace_file):
    """The program before the scopes: every operation's op_name is a
    bare path."""
    for module, ops in OP_NAMES.items():
        trace_file[module] = {i: "jit(train_step)/mul" for i in ops}
    rec = _rec()
    assert all(spec.reader(n).read(rec) is None for n in READERS)


def test_op_names_come_from_a_recorded_traces_hlo_protos(tmp_path):
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("layers"):
            return jnp.tanh(x @ x) * 2.0

    g = jax.jit(f)
    x = jnp.ones((32, 32))
    g(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        g(x).block_until_ready()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = xspace.op_names(path)
    (module,) = [m for m in names if trace.program_name(m) == "jit_f"]
    scoped = {i: n for i, n in names[module].items() if "/layers/" in n}
    assert scoped and all(n.startswith("jit(f)/layers/")
                          for n in scoped.values())
    # every operation the CPU ran names an instruction of the module
    planes = trace.load(path)
    ran = {xspace.instruction(n) for lines in planes.values()
           for evs in lines.values() for n, _, _ in evs}
    assert set(scoped) & ran
    assert xspace.instruction("%fusion.573 = (bf16[8]) fusion(x)") == \
        "fusion.573"
