"""Tensor-parallel sharded serving: rules, parity, and HLO gates.

Two tiers in one file:

* **Always-on (1 device)** — `runtime/sharding.py` rules on serve-shaped
  pytrees (w4a8 packed-nibble planes, per-channel `s_w` co-sharding, the
  non-divisible fallback-to-replication path, the serve pool spec that
  must never shard the global block-id axis), the HLO collective-count /
  pool-all-gather helpers on synthetic modules, and the mesh-factory /
  engine-knob validation errors; plus tp=1 vs tp=4 greedy parity in a
  subprocess with 4 forced host devices, on the XLA path and on the TPU's
  kernel routing (interpret-mode Pallas).
* **Mesh-backed (CI `mesh` job)** — skipped unless the session was
  launched with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
  Bit-exact token-stream parity between a tp=1 engine and tp=2 / tp=4
  engines for greedy+sampled, speculative-decode, and
  preempt/swap-resume serving; ~1/tp per-device pool + packed-weight
  bytes; and a compiled decode wave whose only collectives are the
  canonical TP pair (row-parallel all-reduce, sampled-logit all-gather)
  — no KV-pool all-gather.

Everything runs under ``weights_layout="w4a8"``: the packed path's
integer gemm partials stay below 2^24, so the row-parallel all-reduce is
exact in f32 and sharded serving is *bitwise* tp=1-equivalent, not just
close.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core.precision import parse_policy
from repro.core.qat import (attach_w4a8_exports, attach_w4a8_ref_planes,
                            calibrate_weight_scales)
from repro.models import init_params
from repro.runtime.hlo_analysis import (collective_counts, collective_sites,
                                        pool_allgather_sites)
from repro.runtime.sharding import (param_spec, serve_cache_spec,
                                    _path_str)
from repro.serve.engine import Request, ServeEngine
from repro.serve.spec import SpecConfig

POLICY = "A8d-C8-W4"

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI mesh job sets it)")


class FakeMesh:
    axis_names = ("data", "model")

    def __init__(self, data=4, model=2):
        self.shape = {"data": data, "model": model}


def _w4a8_tree(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    params = calibrate_weight_scales(params, parse_policy(POLICY))
    params = attach_w4a8_exports(params, parse_policy(POLICY))
    return attach_w4a8_ref_planes(params)


# ---------------------------------------------------------------------------
# Sharding rules on serve-shaped pytrees (1 device, fast tier)
# ---------------------------------------------------------------------------

class TestW4A8ParamSpecs:
    def test_export_planes_follow_owner(self):
        """Packed planes shard like the linear they shadow: column owners
        split wq on d_out with s_w/wf on the output channel; row owners
        split wq on the packed d_in/2 axis with s_w replicated."""
        cfg = get_reduced_config("qwen2.5-3b").replace(n_kv_heads=4)
        mesh = FakeMesh(model=2)
        flat, _ = jax.tree_util.tree_flatten_with_path(_w4a8_tree(cfg))
        seen = set()
        for path, leaf in flat:
            p = _path_str(path)
            if "w4a8" not in p.split("/"):
                continue
            parts = p.split("/")
            owner = parts[parts.index("w4a8") - 1]
            key = parts[-1]
            spec = tuple(param_spec(cfg, mesh, p, leaf.shape))
            spec = spec + (None,) * (len(leaf.shape) - len(spec))
            seen.add((owner, key))
            if owner in ("wq", "wk", "wv", "wg", "wu"):    # column-parallel
                if key == "wq":
                    assert spec[-2] == "model" and spec[-1] is None, (p, spec)
                if key == "s_w":
                    assert spec[-1] == "model", (p, spec)
                if key == "wf":
                    assert spec[-1] == "model" and spec[-2] is None, (p, spec)
            elif owner in ("wo", "wd"):                     # row-parallel
                if key == "wq":    # packed d_in/2 still divides (64/2/2)
                    assert spec[-1] == "model" and spec[-2] is None, (p, spec)
                if key == "s_w":   # output channel is device-local: replicate
                    assert spec[-1] is None, (p, spec)
                if key == "wf":
                    assert spec[-2] == "model" and spec[-1] is None, (p, spec)
            elif owner == "head":  # vocab-column-parallel, co-sharded with
                if key == "wq":    # the embed rows it was exported from
                    assert spec[-2] == "model", (p, spec)
                if key == "s_w":
                    assert spec[-1] == "model", (p, spec)
        assert ("wq", "wq") in seen and ("wo", "wq") in seen, seen
        assert ("head", "wq") in seen, "tied-head export missing"

    def test_every_spec_divides(self):
        """No rule may emit an axis that does not divide its dim."""
        cfg = get_reduced_config("qwen2.5-3b").replace(n_kv_heads=4)
        mesh = FakeMesh(model=2)
        flat, _ = jax.tree_util.tree_flatten_with_path(_w4a8_tree(cfg))
        for path, leaf in flat:
            p = _path_str(path)
            spec = param_spec(cfg, mesh, p, leaf.shape)
            assert len(spec) <= len(leaf.shape), (p, spec)
            for dim, ax in zip(leaf.shape[-len(spec):] if len(spec)
                               else (), tuple(spec)):
                if ax is not None:
                    assert dim % mesh.shape[ax] == 0, (p, spec, leaf.shape)

    def test_nondivisible_falls_back_to_replication(self):
        """A mesh axis that divides nothing must replicate everything —
        never raise, never emit a non-dividing axis."""
        cfg = get_reduced_config("qwen2.5-3b")
        mesh = FakeMesh(model=3)        # 3 divides no dim in the reduced cfg
        flat, _ = jax.tree_util.tree_flatten_with_path(_w4a8_tree(cfg))
        for path, leaf in flat:
            p = _path_str(path)
            if "w4a8" not in p.split("/"):
                continue
            spec = tuple(param_spec(cfg, mesh, p, leaf.shape))
            assert all(ax is None for ax in spec), (p, spec)

    def test_odd_packed_axis_replicates(self):
        """Row-parallel wq packs adjacent d_in pairs: when the packed
        d_in/2 axis stops dividing, the leaf replicates instead of
        splitting a nibble pair across devices."""
        cfg = get_reduced_config("qwen2.5-3b")
        spec = param_spec(cfg, FakeMesh(model=2),
                          "segments/0/0/attn/wo/w4a8/wq", (2, 64, 7))
        assert tuple(spec) == (None, None, None) or \
            all(ax is None for ax in tuple(spec))


class TestServeCacheSpec:
    CFG = get_reduced_config("qwen2.5-3b").replace(n_kv_heads=4)

    def test_pool_shards_kv_heads_only(self):
        mesh = FakeMesh(model=2)
        # paged pool leaves: (rep, NB, Hkv, bs, D) / (rep, NB, Hkv, bs)
        kq = serve_cache_spec(self.CFG, mesh,
                              "segments/0/0/self/k_q", (2, 64, 4, 16, 16))
        sk = serve_cache_spec(self.CFG, mesh,
                              "segments/0/0/self/s_k", (2, 64, 4, 16))
        assert tuple(kq) == (None, None, "model", None, None)
        assert tuple(sk) == (None, None, "model", None)

    def test_block_axis_never_shards(self):
        """The leading pool axis is the host allocator's global block-id
        space: it must stay whole even when its size divides every mesh
        axis, or block-table lookups turn into cross-device gathers."""
        for mesh in (FakeMesh(model=2), FakeMesh(data=8, model=2)):
            kq = serve_cache_spec(self.CFG, mesh,
                                  "segments/0/0/self/k_q",
                                  (2, 64, 4, 16, 16))
            assert tuple(kq)[0] is None and tuple(kq)[1] is None

    def test_gqa_nondivisible_replicates(self):
        # Hkv=2 on a 4-way model axis: GQA groups cannot stay local ->
        # the pool replicates rather than erroring
        kq = serve_cache_spec(self.CFG, FakeMesh(model=4),
                              "segments/0/0/self/k_q", (2, 64, 2, 16, 16))
        assert all(ax is None for ax in tuple(kq))

    def test_tables_lengths_replicate(self):
        mesh = FakeMesh(model=2)
        for path, shape in (("block_tbl", (4, 8)), ("position", (4,)),
                            ("segments/0/0/self/length", (2, 4))):
            spec = serve_cache_spec(self.CFG, mesh, path, shape)
            assert all(ax is None for ax in tuple(spec)), (path, spec)


class TestHLOGateHelpers:
    AG_S8 = "%ag = s8[2,131072] all-gather(%pool), dimensions={0}"
    AG_F32 = "%lg = f32[4,256] all-gather(%logits), dimensions={1}"
    AR = "%ar = f32[4,64] all-reduce(%part), to_apply=%add"

    def _mod(self, *lines):
        return "HloModule m\nENTRY %main () -> f32[] {\n" + \
            "\n".join(f"  {l}" for l in lines) + "\n}\n"

    def test_counts_and_sites(self):
        hlo = self._mod(self.AG_F32, self.AR, self.AR)
        assert collective_counts(hlo) == {"all-gather": 1, "all-reduce": 2}
        assert len(collective_sites(hlo)) == 3

    def test_pool_allgather_detection(self):
        hlo = self._mod(self.AG_S8, self.AG_F32, self.AR)
        bad = pool_allgather_sites(hlo)
        assert len(bad) == 1 and bad[0]["bytes"] == 2 * 131072
        # the f32 logit gather and tiny s8 moves are legitimate
        assert pool_allgather_sites(self._mod(self.AG_F32)) == []
        tiny = "%t = s8[8,16] all-gather(%x), dimensions={0}"
        assert pool_allgather_sites(self._mod(tiny)) == []

    def test_start_done_counted_once(self):
        hlo = self._mod(
            "%s = f32[8] all-reduce-start(%x), to_apply=%add",
            "%d = f32[8] all-reduce-done(%s)")
        assert collective_counts(hlo) == {"all-reduce": 1}


class TestMeshValidation:
    def test_local_mesh_rejects_nondividing_tp(self):
        from repro.launch.mesh import make_local_mesh
        n = jax.device_count()
        with pytest.raises(ValueError) as ei:
            make_local_mesh(model_parallel=n + 3)
        assert str(n) in str(ei.value) and str(n + 3) in str(ei.value)

    def test_engine_rejects_mesh_without_model_axis(self):
        mesh = jax.make_mesh((jax.device_count(),), ("data",))
        cfg = get_reduced_config("qwen2.5-3b")
        with pytest.raises(ValueError, match="model"):
            ServeEngine(cfg, None, mesh=mesh)


# ---------------------------------------------------------------------------
# Mesh-backed parity (CI mesh job: 8 forced host devices)
# ---------------------------------------------------------------------------

ENG_KW = dict(policy=POLICY, slots=4, cache_len=128, max_new_cap=32,
              decode_block=4, prefill_bucket=16, kv_layout="paged",
              block_size=16, weights_layout="w4a8")
PREEMPT_KW = dict(policy=POLICY, slots=4, cache_len=128, max_new_cap=32,
                  decode_block=4, prefill_bucket=16, kv_layout="paged",
                  block_size=8, num_blocks=20, admission="optimistic",
                  preempt="last_admitted", weights_layout="w4a8")


def _mixed_reqs(cfg, n=6, max_new=16):
    r = np.random.default_rng(7)
    return [Request(uid=i,
                    prompt=r.integers(1, cfg.vocab_size,
                                      int(r.integers(5, 30))).astype(np.int32),
                    max_new_tokens=max_new, eos_id=-1,
                    temperature=0.0 if i % 2 == 0 else 0.8,
                    top_k=0 if i % 3 == 0 else 8, seed=100 + i)
            for i in range(n)]


def _run(cfg, params, mesh, kw, reqs):
    eng = ServeEngine(cfg, params, mesh=mesh, **kw)
    for rq in reqs:
        eng.submit(rq)
    eng.run_until_drained()
    return [tuple(rq.generated) for rq in reqs], eng.stats(), eng


@pytest.fixture(scope="module")
def served4():
    cfg = get_reduced_config("qwen2.5-3b").replace(n_kv_heads=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, calibrate_weight_scales(params, parse_policy(POLICY))


@pytest.fixture(scope="module")
def served2():
    cfg = get_reduced_config("qwen2.5-3b")      # n_kv_heads=2: GQA groups
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, calibrate_weight_scales(params, parse_policy(POLICY))


def _mesh(tp):
    from repro.launch.mesh import make_local_mesh
    return make_local_mesh(model_parallel=tp)


@needs_mesh
class TestStreamParity:
    @pytest.mark.parametrize("tp", [2, 4])
    def test_greedy_sampled(self, served4, tp):
        cfg, params = served4
        base, st1, _ = _run(cfg, params, None, ENG_KW, _mixed_reqs(cfg))
        got, st2, _ = _run(cfg, params, _mesh(tp), ENG_KW, _mixed_reqs(cfg))
        assert got == base
        assert st2["tp_degree"] == tp and st2["mesh_shape"]["model"] == tp
        # per-device pool + packed-weight bytes scale ~1/tp (the pool's
        # replicated length rows and the non-dividing odd leaves keep it
        # from being exactly 1/tp)
        assert st2["per_device_pool_bytes"] <= 1.2 * st1[
            "per_device_pool_bytes"] / tp
        assert st2["per_device_weight_bytes"] <= 1.2 * st1[
            "per_device_weight_bytes"] / tp

    def test_gqa_grouped_parity(self, served2):
        """n_kv_heads=2 on tp=2: one KV head (4 grouped q heads) per
        device — the grouped decode grid survives per shard."""
        cfg, params = served2
        base, _, _ = _run(cfg, params, None, ENG_KW, _mixed_reqs(cfg))
        got, st, _ = _run(cfg, params, _mesh(2), ENG_KW, _mixed_reqs(cfg))
        assert got == base
        assert st["tp_degree"] == 2

    @pytest.mark.parametrize("tp", [2, 4])
    def test_spec_decode(self, served4, tp):
        cfg, params = served4
        kw = dict(ENG_KW, spec=SpecConfig(k=3, draft_layers=1,
                                          accept_mode="exact"))
        base, st1, _ = _run(cfg, params, None, kw, _mixed_reqs(cfg))
        got, st2, _ = _run(cfg, params, _mesh(tp), kw, _mixed_reqs(cfg))
        assert got == base
        assert st2["spec_waves"] > 0 and st2["spec_accepted"] > 0
        # acceptance itself must be sharding-invariant, not just tokens
        assert st2["spec_accepted"] == st1["spec_accepted"]

    @pytest.mark.parametrize("tp", [2, 4])
    def test_preempt_swap_resume(self, served4, tp):
        cfg, params = served4
        reqs = lambda: _mixed_reqs(cfg, n=8, max_new=20)
        base, st1, _ = _run(cfg, params, None, PREEMPT_KW, reqs())
        got, st2, _ = _run(cfg, params, _mesh(tp), PREEMPT_KW, reqs())
        assert st1["preemptions"] > 0, "workload never preempted"
        assert st2["preemptions"] == st1["preemptions"]
        assert got == base


@needs_mesh
class TestShardedWaveHLO:
    def test_decode_wave_collectives(self, served4):
        """The compiled decode chunk's only collectives are the canonical
        TP set: row-parallel all-reduces (wo / w2, plus the exact
        dynamic-A8 amax reductions) and the sampled-logit all-gather.
        No s8 pool buffer is ever gathered."""
        cfg, params = served4
        mesh = _mesh(2)
        eng = ServeEngine(cfg, params, mesh=mesh, **ENG_KW)
        with mesh:
            hlo = jax.jit(eng._decode_chunk, static_argnums=(2,)).lower(
                eng.params, eng._probe_state(), False).compile().as_text()
        counts = collective_counts(hlo)
        assert counts.get("all-reduce", 0) >= 1, counts
        assert counts.get("all-gather", 0) <= 2, counts
        assert pool_allgather_sites(hlo) == [], \
            [s["line"] for s in pool_allgather_sites(hlo)]

    def test_state_shardings_survive_serving(self, served4):
        """After a full serve run the pool is still sharded on the KV-head
        dim and the token buffers replicated — no drift through the
        donated waves."""
        cfg, params = served4
        _, _, eng = _run(cfg, params, _mesh(2), ENG_KW, _mixed_reqs(cfg))
        kq = eng.state["cache"]["segments"][0]["0"]["self"]["k_q"]
        spec = tuple(kq.sharding.spec) + (None,) * 5
        assert spec[2] == "model", kq.sharding
        assert all(ax is None for ax in tuple(eng.state["out"].sharding.spec))


TP4_PARITY = """
import sys
import jax
import numpy as np
from repro.configs import get_reduced_config
from repro.core.precision import parse_policy
from repro.core.qat import calibrate_weight_scales
from repro.launch.mesh import make_local_mesh
from repro.models import init_params
from repro.serve.engine import Request, ServeEngine
from repro.serve.spec import SpecConfig

kv_heads, kernels = int(sys.argv[1]), sys.argv[2] == "kernels"
if kernels:
    # route the serve path to its Pallas kernels as a TPU does; they stay in
    # interpret mode, fixed when their modules are imported (here, first)
    import repro.kernels.kvq_attn.ops, repro.kernels.w4a8.ops
    jax.default_backend = lambda: "tpu"
cfg = get_reduced_config("qwen2.5-3b").replace(n_kv_heads=kv_heads)
params = calibrate_weight_scales(init_params(cfg, jax.random.PRNGKey(0)),
                                 parse_policy("A8d-C8-W4"))


def streams(mesh):
    eng = ServeEngine(cfg, params, policy="A8d-C8-W4", slots=4,
                      cache_len=128, max_new_cap=16, decode_block=4,
                      prefill_bucket=16, kv_layout="paged", block_size=16,
                      weights_layout="w4a8",
                      spec=SpecConfig(k=3, draft_layers=1), mesh=mesh)
    r = np.random.default_rng(7)
    shared = r.integers(1, cfg.vocab_size, 24)
    reqs = [Request(uid=i, prompt=np.concatenate(
                [shared, r.integers(1, cfg.vocab_size, 3 + 5 * i)]
            ).astype(np.int32), max_new_tokens=12, eos_id=-1)
            for i in range(6)]
    for rq in reqs:
        eng.submit(rq)
    st = eng.run_until_drained()
    return [tuple(rq.generated) for rq in reqs], st


base, _ = streams(None)
got, st = streams(make_local_mesh(model_parallel=4))
assert st["tp_degree"] == 4, st["tp_degree"]
assert st["spec_waves"] > 0 and st["prefix_hit_tokens"] > 0, st
assert got == base, (got, base)
print("TP4_PARITY_OK")
"""


@pytest.mark.parametrize("kv_heads,path", [(4, "xla"), (2, "kernels")])
def test_tp4_greedy_parity_subprocess(kv_heads, path):
    """tp=4 greedy streams equal tp=1 on 4 forced host devices, in a
    subprocess so the suite's own session keeps one device. ``xla``: KV
    heads split over the mesh (4 of them). ``kernels``: the TPU routing
    (Pallas kernels per device under a shard_map) with qwen2.5-3b's two
    KV heads, which tp=4 cannot split, so each device holds them whole."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", TP4_PARITY, str(kv_heads),
                          path], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0 and "TP4_PARITY_OK" in out.stdout, \
        out.stderr[-3000:]


@needs_mesh
class TestProbeMemoKeying:
    def test_mesh_in_probe_key(self, served4):
        """A tp=2 decode_block="auto" probe result must not be replayed
        for tp=1 (different per-step cost) — the memo key carries the
        mesh shape."""
        from repro.serve.engine import _PROBE_CACHE
        cfg, params = served4
        kw = dict(ENG_KW, decode_block="auto")
        ServeEngine(cfg, params, **kw)
        ServeEngine(cfg, params, mesh=_mesh(2), **kw)
        tails = {k[-1] for k in _PROBE_CACHE if k[0] == cfg.name}
        assert None in tails
        assert any(t is not None and ("model", 2) in t for t in tails), tails
