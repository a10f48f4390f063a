"""Per-architecture smoke tests: reduced config, forward + one train step on
CPU, asserting shapes and finiteness (assignment requirement)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.configs.base import SHAPES, TrainConfig
from repro.core.qat import make_ctx
from repro.launch.steps import make_serve_step, make_train_step
from repro.models import decode_step, forward, init_cache, init_params, \
    prefill
from repro.optim import adamw_init


def _batch(cfg, key, B=2, S=16, labels=True):
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        b["patches"] = jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
        b["positions"] = jnp.tile(jnp.arange(S + cfg.vision_tokens),
                                  (3, B, 1))
    if cfg.is_encdec:
        b["frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if labels:
        b["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        b["loss_mask"] = jnp.ones((B, S), jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch, rng):
        cfg = get_reduced_config(arch)
        params = init_params(cfg, rng)
        B, S = 2, 16
        batch = _batch(cfg, rng, B, S, labels=False)
        logits, aux = forward(cfg, params, make_ctx("A8d-C8-W4"), batch)
        S_out = S + (cfg.vision_tokens if cfg.family == "vlm" else 0)
        assert logits.shape == (B, S_out, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    @pytest.mark.slow          # full QAT train step across all 10 archs
    def test_train_step(self, arch, rng):
        cfg = get_reduced_config(arch)
        tcfg = TrainConfig(total_steps=10, ref_steps=10, batch_size=2,
                           seq_len=16)
        params = init_params(cfg, rng)
        opt = adamw_init(params)
        step = make_train_step(cfg, tcfg)
        batch = _batch(cfg, rng)
        new_params, new_opt, metrics = step(params, params, opt, batch,
                                            jnp.int32(0))
        assert bool(jnp.isfinite(metrics["loss"]))
        # params actually changed
        moved = jax.tree.leaves(jax.tree.map(
            lambda a, b: bool(jnp.any(a != b)), params, new_params))
        assert any(moved)

    def test_prefill_decode(self, arch, rng):
        cfg = get_reduced_config(arch)
        params = init_params(cfg, rng)
        ctx = make_ctx("A8d-C8-W4")
        B, S = 2, 16
        batch = _batch(cfg, rng, B, S, labels=False)
        logits, cache = prefill(cfg, params, ctx, batch, cache_budget=S + 8)
        assert logits.shape == (B, 1, cfg.vocab_size)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        l1, cache = decode_step(cfg, params, ctx, tok, cache)
        l2, cache = decode_step(cfg, params, ctx, tok, cache)
        assert l2.shape == (B, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(l2.astype(jnp.float32))))

    def test_full_config_exact_dims(self, arch):
        """The full (non-reduced) config carries the assigned dimensions."""
        cfg = get_config(arch)
        expected = {
            "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151_936),
            "qwen2-7b": (28, 3584, 28, 4, 18944, 152_064),
            "qwen3-14b": (40, 5120, 40, 8, 17408, 151_936),
            "qwen3-32b": (64, 5120, 64, 8, 25600, 151_936),
            "whisper-large-v3": (32, 1280, 20, 20, 5120, 51_866),
            "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163_840),
            "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32_000),
            "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256_000),
            "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151_936),
            "xlstm-125m": (12, 768, 4, 4, 0, 50_304),
            "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100_352),
        }[arch]
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab_size)
        assert got == expected


def test_moe_routing_active():
    """MoE models actually route through multiple experts."""
    cfg = get_reduced_config("mixtral-8x7b")
    params = init_params(cfg, jax.random.PRNGKey(1))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                          cfg.vocab_size)}
    _, aux = forward(cfg, params, make_ctx("A8d-C8-W4"), batch)
    assert float(aux["moe_aux"]) > 0.0


def test_swa_bounds_cache():
    """Sliding-window arch allocates a window-bounded decode cache."""
    cfg = get_reduced_config("mixtral-8x7b")
    ctx = make_ctx("A8d-C8-W4")
    cache = init_cache(cfg, ctx, 2, 1000)
    k_shape = cache["segments"][0]["0"]["self"]["k_q"].shape
    assert k_shape[3] == cfg.sliding_window     # ring-bounded, not 1000


def test_long_context_support_flags():
    assert not get_config("qwen3-32b").supports_long_context
    assert get_config("mixtral-8x7b").supports_long_context
    assert get_config("recurrentgemma-2b").supports_long_context
    assert get_config("xlstm-125m").supports_long_context
