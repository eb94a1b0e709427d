"""jit'd wrapper for flash-decode: model layout + padding (TPU, or `interpret=True`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import check_backend
from repro.kernels.decode_attention.decode_attention import flash_decode


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, n_valid, *, block_k: int = 512, interpret: bool = False):
    """q: (B, Hkv, G, dh); k/v: (B, Hkv, T, dh); n_valid: scalar int32.

    Pads the cache length to a block multiple (padding slots are masked by the
    kernel's n_valid comparison, never attended).
    """
    check_backend(interpret)
    T = k.shape[2]
    bk = min(block_k, T)
    pad = (-T) % bk
    if pad:
        z = ((0, 0), (0, 0), (0, pad), (0, 0))
        k, v = jnp.pad(k, z), jnp.pad(v, z)
    return flash_decode(q, k, v, n_valid, block_k=bk, interpret=interpret)
