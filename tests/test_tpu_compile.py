"""Serve-path Pallas kernels, and the QAT step's layouts, compiled for a
described TPU v5e.

Interpret-mode tests check what the kernels compute; only the TPU compiler
checks that Mosaic accepts their block layouts. Each case lowers a kernel
wrapper of ``ops.py`` at qwen2.5-3b widths with interpret mode off and
compiles it for one chip of a ``v5e:2x2`` topology that is described, not
attached (about a second each). Nothing runs, so results are not checked
here: the interpret-mode parity tests and ``chip_smoke.py`` do that. Only
the TPU's layout assignment shows whether the QAT step re-lays its tied
embedding table, so that is checked here too.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and xdist workers import every test file.
"""
import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config, get_reduced_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.kernels.kvq_attn import ops as kvq
from repro.kernels.w4a8 import ops as w4a8
from repro.launch.specs import batch_struct, opt_struct, param_struct
from repro.launch.steps import make_train_step
from repro.runtime.sharding import (batch_shardings, opt_shardings,
                                    param_shardings)

CFG = get_config("qwen2.5-3b")
D, H, HKV = CFG.resolved_head_dim, CFG.n_heads, CFG.n_kv_heads
SLOTS, NB, BS, T, WINDOW, DENSE_S = 4, 512, 64, 8, 5, 1024
i8, u8, i32, f32 = jnp.int8, jnp.uint8, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def hardware(monkeypatch):
    """Route the wrappers to the compiled kernels, as on a TPU."""
    monkeypatch.setattr(kvq, "_INTERPRET", False)
    monkeypatch.setattr(w4a8, "_INTERPRET", False)


def compile_hlo(fn, *args, **static):
    """Compile ``fn`` for the shapes' devices; return the optimized HLO."""
    return jax.jit(functools.partial(fn, **static)).lower(*args).compile() \
        .as_text()


def assert_kernel(hlo: str):
    assert "tpu_custom_call" in hlo, "no Pallas kernel in the program"


def _pool(shard):
    return (jax.ShapeDtypeStruct((NB, HKV, BS, D), i8, sharding=shard),
            jax.ShapeDtypeStruct((NB, HKV, BS, D), i8, sharding=shard),
            jax.ShapeDtypeStruct((NB, HKV, BS), f32, sharding=shard),
            jax.ShapeDtypeStruct((NB, HKV, BS), f32, sharding=shard))


W4A8_SHAPES = {                      # (M, K, N, bias)
    "d_to_ff": (SLOTS * WINDOW, CFG.d_model, CFG.d_ff, False),
    "ff_to_d": (SLOTS * WINDOW, CFG.d_ff, CFG.d_model, False),
    "qkv_bias": (SLOTS * WINDOW, CFG.d_model, CFG.q_dim + 2 * HKV * D, True),
    "lm_head": (SLOTS, CFG.d_model, CFG.vocab_size, False),
}


@pytest.mark.parametrize("case", list(W4A8_SHAPES))
def test_w4a8_matmul(one_chip, hardware, case):
    M, K, N, bias = W4A8_SHAPES[case]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (S((M, K), i8), S((N, K // 2), u8), S((M, 1), f32), S((N,), f32),
            S((N,), f32) if bias else None)
    assert_kernel(compile_hlo(w4a8.w4a8_matmul, *args, use_pallas=True))


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode(one_chip, hardware, q_dtype):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    hlo = compile_hlo(kvq.kvq_paged_decode_attn, S((SLOTS, H, D), q_dtype),
                      *_pool(one_chip), S((SLOTS, T), i32), S((SLOTS,), i32))
    assert_kernel(hlo)


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
def test_spec_verify(one_chip, hardware, q_dtype):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    hlo = compile_hlo(kvq.kvq_spec_verify_attn,
                      S((SLOTS, WINDOW, H, D), q_dtype), *_pool(one_chip),
                      S((SLOTS, T), i32), S((SLOTS, WINDOW), i32))
    assert_kernel(hlo)


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
def test_dense_decode(one_chip, hardware, q_dtype):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv = S((SLOTS, HKV, DENSE_S, D), i8)
    sc = S((SLOTS, HKV, DENSE_S), f32)
    hlo = compile_hlo(kvq.kvq_decode_attn, S((SLOTS, H, D), q_dtype), kv, kv,
                      sc, sc, S((SLOTS,), i32))
    assert_kernel(hlo)


def test_gather_dequant(one_chip, hardware):
    kp, _, sk, _ = _pool(one_chip)
    tbl = jax.ShapeDtypeStruct((SLOTS, T), i32, sharding=one_chip)
    hlo = compile_hlo(kvq.gather_dequant_paged_kv, kp, sk, tbl,
                      use_pallas=True)
    assert_kernel(hlo)


@pytest.mark.parametrize("leaf", ["int8_payload", "f32_scales"])
def test_pool_block_copy(one_chip, hardware, leaf):
    shape = ((CFG.n_layers, NB, HKV, BS, D) if leaf == "int8_payload"
             else (CFG.n_layers, NB, HKV, BS))
    dt = i8 if leaf == "int8_payload" else f32
    pairs = jax.ShapeDtypeStruct((4,), i32, sharding=one_chip)
    hlo = compile_hlo(kvq.copy_pool_blocks,
                      jax.ShapeDtypeStruct(shape, dt, sharding=one_chip),
                      pairs, pairs, use_pallas=True)
    assert_kernel(hlo)


@pytest.mark.parametrize("tp", [2, 4])
def test_kernels_on_tp_mesh(topo, hardware, tp):
    """Under a serving mesh the kernels run per device in a shard_map: KV
    heads split where ``tp`` divides them (tp=2), whole on every device
    otherwise (tp=4 over qwen2.5-3b's two KV heads)."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4 // tp, tp),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, P())
    heads = NamedSharding(mesh, P(None, "model" if HKV % tp == 0 else None))
    S = lambda shape, dt, s=rep: jax.ShapeDtypeStruct(shape, dt, sharding=s)
    q = S((SLOTS, WINDOW, H, D), jnp.bfloat16)
    hlo = compile_hlo(kvq.kvq_spec_verify_attn, q, *_pool(heads),
                      S((SLOTS, T), i32), S((SLOTS, WINDOW), i32), mesh=mesh)
    assert_kernel(hlo)
    M, K, N = SLOTS, CFG.d_model, CFG.d_ff
    col = NamedSharding(mesh, P("model"))
    hlo = compile_hlo(w4a8.w4a8_matmul, S((M, K), i8), S((N, K // 2), u8, col),
                      S((M, 1), f32), S((N,), f32, col), None,
                      use_pallas=True, mesh=mesh)
    assert_kernel(hlo)


def test_small_pool_blocks_refused_on_hardware(hardware):
    """A pool block under 32 tokens cannot fill an int8 K/V tile: on a TPU
    the paged kernels refuse it instead of quietly running the reference."""
    q = jnp.zeros((1, H, D), f32)
    pool = jnp.zeros((4, HKV, 16, D), i8)
    sc = jnp.zeros((4, HKV, 16), f32)
    with pytest.raises(ValueError, match="block_size >= 32"):
        jax.eval_shape(kvq.kvq_paged_decode_attn, q, pool, pool, sc, sc,
                       jnp.zeros((1, 2), i32), jnp.ones((1,), i32))


@pytest.mark.parametrize("mesh_shape,arch",
                         [(None, "qwen2.5-3b"), ((2, 2), "qwen2.5-3b"),
                          (None, "granite-4.0-h-micro")],
                         ids=["one_chip", "data2_model2", "hybrid_one_chip"])
def test_train_step_keeps_tied_table_row_major(topo, one_chip, mesh_shape,
                                               arch):
    """Left to TPU layout assignment, the tied head's weight gradient comes
    out column-major and the step copies the table and both its AdamW
    moments to that layout and back. With the table's gradient in the
    arguments' layout the step copies none of its arguments: on one chip,
    and on a (data, model) mesh under the dry run's sharding rules, where
    pinning every gradient would copy sharded weights and moments; and in
    the Mamba-2 + attention hybrid, whose embedding is scaled too."""
    V, d = 1024, 128
    cfg = get_reduced_config(arch).replace(
        vocab_size=V, d_model=d, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=2 * d, ssm_heads=8, ssm_head_dim=32)
    assert cfg.tie_embeddings
    params = param_struct(cfg)
    opt = opt_struct(params)
    batch = batch_struct(cfg, ShapeConfig("t", "train", 128, 4),
                         with_labels=True)
    args = (params, params, opt, batch, jax.ShapeDtypeStruct((), i32))
    if mesh_shape is None:
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), args)
        mesh, shardings = contextlib.nullcontext(), {}
    else:
        mesh = Mesh(np.array(topo.devices[:4]).reshape(mesh_shape),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        psh = param_shardings(cfg, mesh, params)
        shardings = {"in_shardings": (
            psh, psh, opt_shardings(psh, opt), batch_shardings(mesh, batch),
            None)}
    step = make_train_step(cfg, TrainConfig())
    with mesh:
        hlo = jax.jit(step, donate_argnums=(0, 2), **shardings).lower(
            *args).compile().as_text()
    relaid = re.findall(rf"= \w+\[(?:{V},{d}|{d},{V})\]\S* "
                        r"(?:copy|transpose)\(", hlo)
    assert relaid == []
    args_copied = re.findall(
        r"= \S+ copy\(.*op_name=\"(?:params|opt_state)[^\"]*", hlo)
    assert args_copied == []
