"""KD loss + QAT state-management tests (the paper's training machinery)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.base import TrainConfig
from repro.core.distill import kd_loss, next_token_loss, silq_loss
from repro.core.precision import PAPER_POLICIES, parse_policy
from repro.core.qat import (ACT_SCALE_KEYS, act_scale_mask,
                            calibrate_weight_scales, export_linear_int,
                            init_linear, make_ctx, merge_act_scales, qlinear,
                            scale_mask)
from repro.launch.steps import make_train_step
from repro.models import forward, head_logits, init_params
from repro.optim import adamw_init


class TestLosses:
    def test_kd_zero_when_matching(self, rng):
        logits = jax.random.normal(rng, (2, 8, 32))
        # KD of identical distributions == entropy; KL part is zero, so the
        # gradient wrt student at the optimum vanishes
        g = jax.grad(lambda s: kd_loss(s, logits))(logits)
        np.testing.assert_allclose(np.asarray(g), 0.0, atol=1e-6)

    def test_kd_decreases_toward_teacher(self, rng):
        t = jax.random.normal(rng, (2, 8, 32))
        s = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        l_far = kd_loss(s, t)
        l_near = kd_loss(0.9 * t + 0.1 * s, t)
        assert float(l_near) < float(l_far)

    def test_next_token_loss_perfect_prediction(self):
        labels = jnp.array([[1, 2, 3]])
        logits = jax.nn.one_hot(labels, 8) * 100.0
        assert float(next_token_loss(logits, labels)) < 1e-3

    def test_masking(self, rng):
        logits = jax.random.normal(rng, (1, 4, 16))
        labels = jnp.zeros((1, 4), jnp.int32)
        m1 = jnp.array([[1.0, 1.0, 0.0, 0.0]])
        m2 = jnp.array([[1.0, 1.0, 1.0, 1.0]])
        l1 = next_token_loss(logits, labels, m1)
        l2 = next_token_loss(logits[:, :2], labels[:, :2],
                             jnp.ones((1, 2)))
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)

    def test_silq_ratio_interpolates(self, rng):
        s = jax.random.normal(rng, (2, 4, 16))
        t = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16))
        labels = jnp.zeros((2, 4), jnp.int32)
        lk = silq_loss(s, t, labels, kd_ratio=1.0)
        ln = silq_loss(s, t, labels, kd_ratio=0.0)
        lm = silq_loss(s, t, labels, kd_ratio=0.5)
        np.testing.assert_allclose(float(lm),
                                   0.5 * float(lk) + 0.5 * float(ln),
                                   rtol=1e-5)

    def test_temperature_scaling_bounded_gradient(self, rng):
        """T^2 factor keeps gradient magnitude T-invariant (Hinton)."""
        s = jax.random.normal(rng, (2, 4, 64))
        t = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 64))
        g1 = jax.grad(lambda s: kd_loss(s, t, 1.0))(s)
        g2 = jax.grad(lambda s: kd_loss(s, t, 2.0))(s)
        r = float(jnp.linalg.norm(g2) / jnp.linalg.norm(g1))
        assert 0.3 < r < 3.0


class TestPolicies:
    @pytest.mark.parametrize("name", PAPER_POLICIES)
    def test_parse(self, name):
        p = parse_policy(name)
        assert p.name == name

    def test_parse_fields(self):
        p = parse_policy("A8d-C4-W4")
        assert (p.act_bits, p.act_dynamic, p.cache_bits, p.weight_bits) == \
            (8, True, 4, 4)
        p = parse_policy("A8s-C8-W4")
        assert not p.act_dynamic
        assert parse_policy("A16-C16-W16").enabled is False

    def test_bad_name(self):
        with pytest.raises(ValueError):
            parse_policy("W4-only")


class TestQATState:
    def test_scale_masks(self, rng):
        cfg = get_reduced_config("qwen2.5-3b")
        params = init_params(cfg, rng)
        smask = scale_mask(params)
        amask = act_scale_mask(params)
        flat_s = jax.tree_util.tree_flatten_with_path(smask)[0]
        flat_a = jax.tree_util.tree_flatten_with_path(amask)[0]
        n_scales = sum(bool(v) for _, v in flat_s)
        n_act = sum(bool(v) for _, v in flat_a)
        assert n_scales > n_act > 0     # weight scales not in the boost set
        for path, v in flat_a:
            if v:
                key = str(path[-1].key)
                assert key in ACT_SCALE_KEYS

    def test_weight_calibration_touches_all_s_w(self, rng):
        cfg = get_reduced_config("mixtral-8x7b")
        params = init_params(cfg, rng)
        cal = calibrate_weight_scales(params, parse_policy("A8d-C8-W4"))
        changed = unchanged = 0
        flat0 = jax.tree_util.tree_flatten_with_path(params)[0]
        flat1 = jax.tree_util.tree_flatten_with_path(cal)[0]
        for (p0, l0), (p1, l1) in zip(flat0, flat1):
            key = str(p0[-1].key) if hasattr(p0[-1], "key") else ""
            if key == "s_w":
                if bool(jnp.all(l0 == l1)):
                    unchanged += 1
                else:
                    changed += 1
        assert changed > 0 and unchanged == 0

    def test_calibration_collect_and_merge(self, rng):
        cfg = get_reduced_config("qwen3-14b")
        params = init_params(cfg, rng)
        policy = parse_policy("A8s-C8-W4")
        ctx = make_ctx(policy, mode="calib")
        batch = {"tokens": jax.random.randint(rng, (2, 16), 0,
                                              cfg.vocab_size)}
        _, aux = forward(cfg, params, ctx, batch, collect_stats=True)
        merged = merge_act_scales(params, [aux["qstats"]], policy)
        s0 = params["segments"][0]["0"]["attn"]["wq"]["s_in"]
        s1 = merged["segments"][0]["0"]["attn"]["wq"]["s_in"]
        assert bool(jnp.any(s0 != s1))
        assert bool(jnp.all(s1 > 0))

    def test_export_linear_int4_packing(self, rng):
        p = init_linear(rng, 32, 16)
        exp = export_linear_int(p, 4)
        assert exp["wq"].shape == (16, 16)      # (d_out, d_in/2) packed
        assert exp["wq"].dtype == jnp.uint8
        assert exp["packed"]

    def test_qlinear_baseline_policy_is_exact(self, rng):
        p = init_linear(rng, 16, 8)
        x = jax.random.normal(rng, (4, 16))
        y_off = qlinear(make_ctx("A16-C16-W16", mode="off"), x, p)
        np.testing.assert_allclose(np.asarray(y_off),
                                   np.asarray(x @ p["w"]), rtol=1e-5)

    def test_quantization_error_shrinks_with_bits(self, rng):
        p = init_linear(rng, 64, 32)
        from repro.core.calibration import mse_weight_scale
        x = jax.random.normal(rng, (8, 64))
        y_ref = x @ p["w"]
        errs = []
        for bits in (2, 4, 8):
            p2 = dict(p)
            p2["s_w"] = mse_weight_scale(p["w"], bits)
            ctx = make_ctx(f"A16-C16-W{bits}".replace("A16", "A8d")
                           .replace("C16", "C8"))
            y = qlinear(ctx, x, p2, weight_bits=bits, act_bits=16)
            errs.append(float(jnp.mean((y - y_ref) ** 2)))
        assert errs[0] > errs[1] > errs[2]


class TestTiedHead:
    """The tied head fake-quantizes and contracts the embedding table as
    stored, (V, d): the same numbers as the linear on ``embed.w.T`` it
    replaces, and a train step that never transposes the table."""

    @pytest.mark.parametrize("policy,mode", [
        ("A8d-C8-W4", "train"),       # the student
        ("A8s-C8-W4", "train"),       # static input scale: LSQ on s_in
        ("A16-C16-W16", "off"),       # the teacher
        ("A8s-C8-W4", "calib"),       # fills col["head"]
    ])
    def test_matches_linear_on_transposed_table(self, rng, policy, mode):
        cfg = get_reduced_config("qwen2.5-3b")
        assert cfg.tie_embeddings and cfg.vocab_size != cfg.d_model
        params = calibrate_weight_scales(init_params(cfg, rng),
                                         parse_policy("A8d-C8-W4"))
        ctx = make_ctx(policy, mode=mode)
        hb = ctx.policy.head_bits
        kx, kg = jax.random.split(jax.random.fold_in(rng, 7))
        x = (2.0 * jax.random.normal(kx, (2, 16, cfg.d_model))
             ).astype(jnp.bfloat16)
        w, s_w = params["embed"]["w"], params["head"]["s_w"]
        s_in = jnp.float32(0.05)          # clips part of x
        cols = {}

        def tied(w, s_w, s_in, x):
            p = dict(params, embed={"w": w},
                     head={"s_w": s_w, "s_in": s_in})
            col = {}
            y = head_logits(cfg, p, ctx, x, col)
            cols["tied"] = col["head"]
            return y

        def linear(w, s_w, s_in, x):
            cols["linear"] = {}
            return qlinear(ctx, x, {"w": w.T, "s_w": s_w, "s_in": s_in},
                           cols["linear"], act_bits=hb, weight_bits=hb)

        args = (w, s_w, s_in, x)
        y_t, vjp_t = jax.vjp(tied, *args)
        y_l, vjp_l = jax.vjp(linear, *args)
        assert y_t.shape == (2, 16, cfg.vocab_size)
        np.testing.assert_array_equal(np.asarray(y_t), np.asarray(y_l))
        g = jax.random.normal(kg, y_t.shape).astype(y_t.dtype)
        for name, a, b in zip(("embed.w", "head.s_w", "head.s_in", "x"),
                              vjp_t(g), vjp_l(g)):
            assert a.shape == b.shape, name
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        if mode == "calib":
            tied(*args), linear(*args)     # the collectors, outside the vjp
            assert set(cols["tied"]) == {"s_in"}
            np.testing.assert_array_equal(np.asarray(cols["tied"]["s_in"]),
                                          np.asarray(cols["linear"]["s_in"]))

    @pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen2-7b"])
    def test_train_step_never_transposes_the_table(self, rng, arch):
        cfg = get_reduced_config(arch)
        V, d = cfg.vocab_size, cfg.d_model
        params = jax.eval_shape(lambda k: init_params(cfg, k), rng)
        opt = jax.eval_shape(adamw_init, params)
        batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
                 for k in ("tokens", "labels")}
        step = make_train_step(cfg, TrainConfig(total_steps=10, ref_steps=10,
                                                batch_size=2, seq_len=16))
        args = (params, params, opt, batch,
                jax.ShapeDtypeStruct((), jnp.int32))
        text = jax.jit(step).lower(*args).as_text()
        table = (f"tensor<{V}x{d}x", f"tensor<{d}x{V}x")
        transposes = [ln for ln in text.splitlines()
                      if "stablehlo.transpose" in ln
                      and any(t in ln for t in table)]
        if cfg.tie_embeddings:
            assert "w" not in params["head"]
            assert params["head"]["s_w"].shape == (1, V)
            assert transposes == [], transposes
        else:
            assert params["head"]["w"].shape == (d, V)
        # the step hands back the tree it was given, leaf for leaf
        new_params, _, _ = jax.eval_shape(step, *args)
        assert jax.tree.structure(new_params) == jax.tree.structure(params)
        for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(params)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
