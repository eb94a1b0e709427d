"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Off-TPU the wrappers refuse to run unless `interpret=True` is passed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention_bhsd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm, rmsnorm_residual
from repro.kernels.rmsnorm.ref import rmsnorm_ref, rmsnorm_residual_ref

# JAX compile-heavy: excluded from the fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,dh,win",
    [
        (2, 4, 4, 256, 64, None),  # MHA
        (1, 8, 2, 256, 128, None),  # GQA 4:1
        (2, 4, 2, 384, 64, 128),  # GQA + sliding window
        (1, 2, 1, 300, 32, None),  # non-multiple seq (padding path)
    ],
)
def test_flash_attention_vs_oracle(dtype, B, Hq, Hkv, S, dh, win):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, dh), jnp.float32).astype(dtype)
    out = flash_attention_bhsd(q, k, v, window=win, interpret=True)
    ref = attention_ref(q, k, v, window=win)
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    assert err < TOL[dtype], err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hkv,G,T,dh,nv",
    [
        (2, 4, 2, 512, 64, 300),
        (1, 2, 6, 1024, 128, 1024),
        (2, 8, 1, 512, 64, 1),  # single valid slot
        (1, 2, 4, 600, 32, 77),  # non-multiple cache (padding path)
    ],
)
def test_decode_attention_vs_oracle(dtype, B, Hkv, G, T, dh, nv):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, T, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, T, dh), jnp.float32).astype(dtype)
    out = decode_attention(q, k, v, jnp.int32(nv), interpret=True)
    ref = decode_attention_ref(q, k, v, jnp.int32(nv))
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    assert err < TOL[dtype], err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,D", [(256, 512), (300, 256), (64, 1024)])
def test_rmsnorm_vs_oracle(dtype, T, D):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (T, D), jnp.float32).astype(dtype)
    res = jax.random.normal(jax.random.fold_in(key, 1), (T, D), jnp.float32).astype(dtype)
    sc = jax.random.normal(jax.random.fold_in(key, 2), (D,), jnp.float32)
    err = np.abs(
        np.asarray(rmsnorm(x, sc, interpret=True), np.float32) - np.asarray(rmsnorm_ref(x, sc), np.float32)
    ).max()
    assert err < TOL[dtype]
    y1, r1 = rmsnorm_residual(x, res, sc, interpret=True)
    y2, r2 = rmsnorm_residual_ref(x, res, sc)
    for a, b in ((y1, y2), (r1, r2)):
        assert np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max() < TOL[dtype]


def test_flash_attention_matches_model_layout_wrapper():
    from repro.kernels.flash_attention.ops import flash_attention

    key = jax.random.PRNGKey(3)
    B, S, Hkv, G, dh = 1, 128, 2, 2, 32
    q = jax.random.normal(key, (B, S, Hkv, G, dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, dh), jnp.float32)
    out = flash_attention(q, k, v, interpret=True)
    from repro.models.attention import _dense_attention

    pos = jnp.arange(S)
    ref = _dense_attention(q, k, v, pos, pos, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("call", [
    lambda x: flash_attention_bhsd(x, x, x),
    lambda x: decode_attention(x, x, x, jnp.int32(1)),
    lambda x: rmsnorm(x, x[0, 0, 0]),
    lambda x: rmsnorm_residual(x, x, x[0, 0, 0]),
], ids=["flash_attention", "decode_attention", "rmsnorm", "rmsnorm_residual"])
def test_kernel_wrappers_refuse_without_interpret_off_tpu(call):
    if jax.default_backend() == "tpu":
        pytest.skip("the refusal is off-TPU only")
    x = jnp.zeros((1, 1, 128, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="interpret=True"):
        call(x)
