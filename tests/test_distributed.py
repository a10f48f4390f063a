"""Distribution-layer tests: sharding rules, HLO collective analysis,
gradient compression, and a miniature multi-device dry run. Multi-device
cases run in a subprocess so the main test session keeps 1 CPU device."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.hlo_analysis import _shape_bytes, analyze_collectives

# multi-device subprocess compiles put the whole module in the slow tier
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


class TestHLOAnalysis:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[16,768]") == 16 * 768 * 4
        assert _shape_bytes("(bf16[8,4]{1,0}, s8[2,2])") == 64 + 4
        assert _shape_bytes("pred[10]") == 10

    def test_loop_multiplier(self):
        hlo = textwrap.dedent("""\
        HloModule test
        %cond (p: (s32[], f32[8])) -> pred[] {
          %p = (s32[], f32[8]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %n = s32[] constant(7)
          ROOT %lt = pred[] compare(%i, %n), direction=LT
        }
        %body (p2: (s32[], f32[8])) -> (s32[], f32[8]) {
          %p2 = (s32[], f32[8]) parameter(0)
          %x = f32[8] get-tuple-element(%p2), index=1
          %ar = f32[8] all-reduce(%x), to_apply=%add
          ROOT %t = (s32[], f32[8]) tuple(%i2, %ar)
        }
        ENTRY %main (a: f32[8]) -> f32[8] {
          %a = f32[8] parameter(0)
          %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
          %big = f32[128] all-gather(%a), dimensions={0}
          ROOT %r = f32[8] get-tuple-element(%w), index=1
        }
        """)
        r = analyze_collectives(hlo)
        assert r["by_op"]["all-reduce"] == 7 * 8 * 4   # trip count 7
        assert r["by_op"]["all-gather"] == 128 * 4

    def test_real_compiled_module(self):
        """End-to-end on an actual compiled GSPMD program: a scan of
        column->row tensor-parallel matmul pairs — the serve wave's layer
        structure in miniature. The row-parallel product forces one
        all-reduce per scan step, and the analyzer must recover the scan
        trip count as the site's loop multiplier."""
        code = """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(model_parallel=8)
        w1 = jax.device_put(jnp.ones((5, 16, 64)),
                            NamedSharding(mesh, P(None, None, "model")))
        w2 = jax.device_put(jnp.ones((5, 64, 16)),
                            NamedSharding(mesh, P(None, "model", None)))
        def f(x, w1, w2):
            def body(c, ws):
                a, b = ws
                h = jnp.maximum(c @ a, 0.0)
                return c + h @ b, None
            c, _ = jax.lax.scan(body, x, (w1, w2))
            return c
        with mesh:
            hlo = jax.jit(f).lower(jnp.ones((4, 16)), w1,
                                   w2).compile().as_text()
        from repro.runtime.hlo_analysis import (analyze_collectives,
                                                collective_counts,
                                                pool_allgather_sites)
        r = analyze_collectives(hlo)
        mults = {s["mult"] for s in r["per_site"]}
        assert r["by_op"].get("all-reduce"), "row-parallel all-reduce lost"
        assert 5.0 in mults, mults   # scan trip count recovered
        assert collective_counts(hlo).get("all-reduce", 0) >= 1
        assert pool_allgather_sites(hlo) == []   # f32 program: no s8 pool
        print("OK")
        """
        r = run_subprocess(code)
        assert "OK" in r.stdout, r.stdout + r.stderr


class TestShardingRules:
    def test_param_specs_divisible(self):
        """Every spec produced for every arch divides its dims on the
        production mesh axis sizes (checked symbolically, 1 device)."""
        from repro.configs import ARCH_IDS, get_config
        from repro.launch.specs import param_struct
        from repro.runtime.sharding import param_spec, _path_str

        class FakeMesh:
            shape = {"pod": 2, "data": 16, "model": 16}
            axis_names = ("pod", "data", "model")

        for arch in ARCH_IDS:
            cfg = get_config(arch)
            ps = param_struct(cfg)
            flat, _ = jax.tree_util.tree_flatten_with_path(ps)
            for path, leaf in flat:
                spec = param_spec(cfg, FakeMesh(), _path_str(path),
                                  leaf.shape)
                assert len(spec) <= len(leaf.shape), (arch, path)
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None:
                        continue
                    size = FakeMesh.shape[ax] if isinstance(ax, str) else \
                        int(np.prod([FakeMesh.shape[a] for a in ax]))
                    assert dim % size == 0, (arch, _path_str(path), spec)

    def test_moe_expert_parallel_choice(self):
        """64-expert moonshot shards experts; 8-expert mixtral uses TP."""
        from repro.configs import get_config
        from repro.runtime.sharding import param_spec

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")

        moon = param_spec(get_config("moonshot-v1-16b-a3b"), FakeMesh(),
                          "segments/0/0/moe/wg/w", (48, 64, 2048, 1408))
        assert tuple(moon) == (None, "model", None, None)
        mix = param_spec(get_config("mixtral-8x7b"), FakeMesh(),
                         "segments/0/0/moe/wg/w", (32, 8, 4096, 14336))
        assert tuple(mix) == (None, None, None, "model")


class TestCompression:
    def test_compressed_psum_matches_mean(self):
        """int8-compressed all-reduce approximates the true mean; error
        feedback drives the *accumulated* bias to zero over steps."""
        code = """
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.runtime.compression import compressed_psum, \\
            init_error_feedback
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        true_mean = jnp.mean(g_global, 0)

        def step(g, e):
            gs, e2 = compressed_psum({"w": g}, e, "data")
            return gs["w"], e2

        f = jax.shard_map(step, mesh=mesh,
                      in_specs=(P("data"), {"w": P("data")}),
                      out_specs=(P("data"), {"w": P("data")}))
        err = init_error_feedback({"w": g_global})
        out, err = f(g_global, err)
        rel = float(jnp.linalg.norm(out[0] - true_mean)
                    / jnp.linalg.norm(true_mean))
        assert rel < 0.02, rel
        # error feedback: residual bounded by one quantization step
        assert float(jnp.max(jnp.abs(err["w"]))) < float(
            jnp.max(jnp.abs(g_global))) / 100.0
        print("OK", rel)
        """
        r = run_subprocess(code)
        assert "OK" in r.stdout, r.stdout + r.stderr


class TestMiniDryRun:
    @pytest.mark.slow
    def test_mini_mesh_train_compile(self):
        """A reduced arch train step lowers + compiles on a (2,2,2) pod
        mesh with the real sharding rules — the dry-run path in miniature."""
        code = """
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.configs.base import ShapeConfig, TrainConfig
        from repro.launch.specs import (batch_struct, opt_struct,
                                        param_struct, sds)
        from repro.launch.steps import make_train_step
        from repro.runtime.sharding import (batch_shardings, opt_shardings,
                                            param_shardings)
        cfg = get_reduced_config("qwen2.5-3b").replace(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab_size=256)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", "train", 64, 8)
        ps = param_struct(cfg)
        psh = param_shardings(cfg, mesh, ps)
        bs = batch_struct(cfg, shape, with_labels=True)
        with mesh:
            fn = make_train_step(cfg, TrainConfig())
            low = jax.jit(fn, in_shardings=(
                psh, psh, opt_shardings(psh, opt_struct(ps)),
                batch_shardings(mesh, bs), None)).lower(
                ps, ps, opt_struct(ps), bs, sds((), jnp.int32))
            comp = low.compile()
        # jax 0.4.3x returns a one-element list of cost dicts
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        assert ca["flops"] > 0
        print("OK", int(ca["flops"]))
        """
        r = run_subprocess(code, devices=8)
        assert "OK" in r.stdout, r.stdout + r.stderr
