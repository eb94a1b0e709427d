"""Attention paths: chunked == dense (incl. SWA), flash-VJP values + grads."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models.attention import _chunked_attention, _dense_attention
from repro.models.flash_vjp import flash_attention_vjp

# JAX compile-heavy: excluded from the fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow


def _rand(key, *shape):
    return 0.3 * jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_matches_dense(window, chunk):
    key = jax.random.PRNGKey(0)
    B, S, Hkv, G, dh = 2, 96, 2, 3, 16
    q = _rand(key, B, S, Hkv, G, dh)
    k = _rand(jax.random.fold_in(key, 1), B, S, Hkv, dh)
    v = _rand(jax.random.fold_in(key, 2), B, S, Hkv, dh)
    pos = jnp.arange(S)
    ref = _dense_attention(q, k, v, pos, pos, window)
    for differentiable in (False, True):
        out = _chunked_attention(q, k, v, window, chunk, differentiable=differentiable)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("window", [None, 40])
def test_flash_vjp_value_and_grads(window):
    key = jax.random.PRNGKey(3)
    B, S, Hkv, G, dh, chunk = 1, 96, 1, 4, 16, 32
    q = _rand(key, B, S, Hkv, G, dh)
    k = _rand(jax.random.fold_in(key, 1), B, S, Hkv, dh)
    v = _rand(jax.random.fold_in(key, 2), B, S, Hkv, dh)
    pos = jnp.arange(S)
    f1 = lambda q, k, v: (flash_attention_vjp(q, k, v, window, chunk) ** 2).sum()
    f2 = lambda q, k, v: (_dense_attention(q, k, v, pos, pos, window) ** 2).sum()
    v1, g1 = jax.value_and_grad(f1, argnums=(0, 1, 2))(q, k, v)
    v2, g2 = jax.value_and_grad(f2, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(v1) - float(v2)) / abs(float(v2)) < 1e-5
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_decode_swa_ring_buffer_positions():
    """Ring-buffer decode must attend exactly the last `window` tokens."""

    from repro.configs import get_config
    from repro.models.transformer import Model

    cfg = get_config("h2o_danube_1p8b").reduced(sliding_window=8)
    m = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init(key)
    B, S = 1, 40
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    x, _ = m.forward(params, {"tokens": tokens})
    ref = np.asarray(m._head(params, x))
    logits, cache = jax.jit(lambda p, b: m.prefill(p, b, max_len=S))(
        params, {"tokens": tokens[:, :24]}
    )
    dec = jax.jit(lambda p, c, t: m.decode_step(p, c, t))
    errs = []
    for t in range(24, S):
        logits, cache = dec(params, cache, tokens[:, t : t + 1])
        errs.append(np.abs(np.asarray(logits) - ref[:, t]).max())
    # cache holds only 8 slots yet matches the full-window forward exactly
    assert cache["layers"]["kv"]["k"].shape[1] == 8
    assert max(errs) < 2e-3
