"""jit'd wrappers for the fused RMSNorm kernel (rank-agnostic, padding).

TPU only, unless `interpret=True` asks for the Pallas interpreter."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import check_backend
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_fwd, rmsnorm_residual_fwd


@functools.partial(jax.jit, static_argnames=("interpret",))
def rmsnorm(x, scale, *, interpret: bool = False):
    check_backend(interpret)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    T = x2.shape[0]
    br = min(256, T)
    pad = (-T) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_fwd(x2, scale, block_rows=br, interpret=interpret)
    return out[:T].reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rmsnorm_residual(x, res, scale, *, interpret: bool = False):
    check_backend(interpret)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r2 = res.reshape(-1, shape[-1])
    T = x2.shape[0]
    br = min(256, T)
    pad = (-T) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        r2 = jnp.pad(r2, ((0, pad), (0, 0)))
    y, r = rmsnorm_residual_fwd(x2, r2, scale, block_rows=br, interpret=interpret)
    return y[:T].reshape(shape), r[:T].reshape(shape)
