"""The Mamba-2 mixer and the granite-4.0-h hybrid on the CPU at tiny sizes:
the chunked SSD against the per-token recurrence it computes, the hybrid's
unquantized forward against the plain reference
(``bench/reference/granite_hybrid.py``) on seeded random weights, and
prefill then decode through the dense cache against the full forward."""
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.adapters import granite_hybrid as ad  # noqa: E402
from bench.reference import granite_hybrid as ref  # noqa: E402
from repro.core.qat import make_ctx  # noqa: E402
from repro.models import decode_step, forward, prefill  # noqa: E402
from repro.models import blocks, model, recurrent  # noqa: E402

CHUNK = 8
SSD_B, SSD_H = 2, 3            # batch and heads of the SSD's test inputs


def _bf16(t):
    return t.astype(jnp.bfloat16).astype(jnp.float32)


# --------------------------------------------------------------------------
# chunked SSD
# --------------------------------------------------------------------------

def _ssd_inputs(S, key):
    ks = jax.random.split(key, 6)
    B, H, P, N = SSD_B, SSD_H, 4, 5
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    h0 = jax.random.normal(ks[5], (B, H, P, N))
    return x, dt, A, Bm, Cm, h0


def _sequential(x, dt, A, Bm, Cm, h0):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t."""
    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = h * jnp.exp(dtt * A)[..., None, None] + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt)
        return h, jnp.einsum("bhpn,bn->bhp", h, ct)
    mv = lambda t: jnp.moveaxis(t, 1, 0)            # noqa: E731
    h, ys = jax.lax.scan(step, h0, (mv(x), mv(dt), mv(Bm), mv(Cm)))
    return mv(ys), h


def _chunked(x, dt, A, Bm, Cm, h0):
    return recurrent.ssd_chunked(x, dt, A, Bm, Cm, CHUNK, state0=h0)


def _close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b)))
    return float(jnp.max(jnp.abs(a - b))) <= tol * scale


def _assert_matches_sequential(S):
    """Values within 3e-5 and gradients (every input, ``state0`` among
    them) within 1e-4 of the largest, against the per-token recurrence."""
    args = _ssd_inputs(S, jax.random.PRNGKey(S))
    ry, rh = jax.random.normal(jax.random.PRNGKey(99), (2,))
    want = _sequential(*args)
    got = _chunked(*args)
    for g, w in zip(got, want):
        assert _close(g, w, 3e-5)

    def loss(fn, *a):
        y, h = fn(*a)
        return ry * jnp.sum(jnp.sin(y)) + rh * jnp.sum(jnp.cos(h))

    argn = tuple(range(6))
    g_want = jax.grad(functools.partial(loss, _sequential), argn)(*args)
    g_got = jax.grad(functools.partial(loss, _chunked), argn)(*args)
    for g, w in zip(g_got, g_want):
        assert _close(g, w, 1e-4)
    return args, want


# S: two whole chunks, a ragged last chunk, one chunk shorter than CHUNK,
# four whole chunks (as in the benchmark's cell), five with the last ragged
@pytest.mark.parametrize("S", [16, 13, 5, 32, 37])
def test_chunked_ssd_matches_the_sequential_recurrence(S):
    """Both are f32: they differ by summation order alone (~1e-6 of the
    values), while rounding the SSD's inputs to bf16 moves them by ~1e-3,
    so 3e-5 of the largest value (and 1e-4 for gradients, whose sums are
    longer) admits the one and not the other. From two chunks on, the
    states entering the chunks come from the chunk-level decay matrix."""
    args, want = _assert_matches_sequential(S)
    rounded = _chunked(*[_bf16(a) for a in args])
    assert not _close(rounded[0], want[0], 3e-5)


# chunks a group may hold: 37 tokens are 5 chunks, so 5 groups of 1, or 3
# groups of 2 of which the last is padded
@pytest.mark.parametrize("per_group", [1, 2])
def test_chunk_groups_carry_the_state_between_them(per_group, monkeypatch):
    """Where one group may not hold every chunk (a long prompt), groups of
    chunks run in turn, each from the state the last left: the same
    recurrence, values and gradients."""
    monkeypatch.setattr(recurrent, "SSD_GROUP_BYTES",
                        per_group * SSD_B * SSD_H * CHUNK * CHUNK * 4)
    _assert_matches_sequential(37)


def test_ssd_backward_at_the_cells_shapes_runs_no_loop():
    """The gradient through the SSD at the granite cell's shapes (1 x 1024
    tokens, 64 heads of 64, state 128, chunk 256), lowered from shapes
    alone: no ``while`` and no ``dynamic_update_slice``, so no chunk scan
    stacks its blocks in the QAT step."""
    B, S, H, P, N, L = 1, 1024, 64, 64, 128, 256
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, f32) for s in
              [(B, S, H, P), (B, S, H), (H,), (B, S, N), (B, S, N)]]

    def loss(x, dt, A, Bm, Cm):
        y, h = recurrent.ssd_chunked(x, dt, A, Bm, Cm, L)
        return jnp.sum(y) + jnp.sum(h)

    text = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(
        *shapes).as_text()
    assert "stablehlo.dot_general" in text
    assert "stablehlo.while" not in text
    assert "stablehlo.dynamic_update_slice" not in text


# --------------------------------------------------------------------------
# the hybrid against the reference
# --------------------------------------------------------------------------

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "shared_intermediate_size": 128, "vocab_size": 256,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_chunk_size": CHUNK}


@pytest.fixture(scope="module")
def tiny():
    """The benchmark's granite configuration at tiny widths, one period
    of layers kept, its weights from a seed and the program's f32 tree
    over them."""
    c = json.loads((ROOT / "bench" / "configs" /
                    "granite-4.0-h-micro-qat10.json").read_text())
    c.update(TINY, name="granite-tiny")
    w = ref.make_weights(c, ref.seed_key(3))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ad.program_params(c, w))
    return c, w, ad.model_config(c), params


def test_unquantized_forward_matches_the_reference(tiny, monkeypatch):
    """Program (f32 weights, quantizers off) against the f32 reference, 29
    tokens over chunks of 8. The attention's probabilities are held in f32
    here (the program keeps them bf16 in training, which alone moves the
    logits by 4e-4 of their largest value); then the two differ by 8e-7,
    while the SSD on bf16 inputs moves them by 6e-4 and a bf16 head input
    by 1.6e-3: 2e-5 admits the f32 path alone."""
    c, w, cfg, params = tiny
    monkeypatch.setattr(blocks, "blockwise_attention", functools.partial(
        blocks.blockwise_attention, p_dtype=jnp.float32))
    toks = jax.random.randint(jax.random.PRNGKey(4), (29,), 0,
                              c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.train_logits(c, w, toks))
    ctx = make_ctx("A16-C16-W16", mode="off")

    def gap():
        got = forward(cfg, params, ctx, {"tokens": toks[None]})[0][0]
        return float(np.max(np.abs(np.asarray(got) - want))
                     / np.max(np.abs(want)))

    assert gap() < 2e-5
    real = recurrent.ssd_chunked
    monkeypatch.setattr(recurrent, "ssd_chunked", lambda x, dt, A, Bm, Cm,
                        chunk, state0=None: real(_bf16(x), _bf16(dt), A,
                                                 _bf16(Bm), _bf16(Cm),
                                                 chunk, state0))
    assert gap() > 2e-5
    monkeypatch.setattr(recurrent, "ssd_chunked", real)
    head = model.head_logits
    monkeypatch.setattr(model, "head_logits", lambda cfg, params, ctx, x,
                        col=None: head(cfg, params, ctx, _bf16(x), col))
    assert gap() > 2e-5


def test_prefill_then_decode_matches_the_full_forward(tiny):
    """Prefill 11 tokens (a chunk and a ragged one), then decode 9 through
    the dense cache, each fed the true next token: every logit against
    the full forward's at the same position. Quantizers off; the cache
    still stores the SSD state, the conv's inputs and K/V in bf16, which
    moves the logits by up to 4.2e-3 of their largest value; 2e-2 allows
    that, while an SSD state left one step behind moves them by 0.17 and
    a conv tail never updated by 1.4."""
    c, w, cfg, params = tiny
    ctx = make_ctx("A16-C16-W16", mode="off")
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 20), 0,
                              c["vocab_size"])
    full = np.asarray(forward(cfg, params, ctx, {"tokens": toks})[0][0])
    S0 = 11
    logits, cache = prefill(cfg, params, ctx, {"tokens": toks[:, :S0]},
                            cache_budget=20)
    got = [np.asarray(logits[0, -1])]
    for t in range(S0, 19):
        logits, cache = decode_step(cfg, params, ctx, toks[:, t:t + 1],
                                    cache)
        got.append(np.asarray(logits[0, -1]))
    got = np.stack(got)
    want = full[S0 - 1:19]
    assert np.max(np.abs(got - want)) < 2e-2 * np.max(np.abs(want))
