"""jit'd public wrapper for the flash-attention kernel.

Handles layout adaptation ((B, S, Hkv, G, dh) model layout <-> (B, H, S, dh)
kernel layout), block-size selection and padding to block multiples. On TPU
the kernel compiles to Mosaic; elsewhere `interpret=True` must be passed to run
it in the Pallas interpreter (correctness tests), or the call raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import check_backend
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))
def flash_attention_bhsd(q, k, v, *, window=None, block_q=128, block_k=128,
                         interpret: bool = False):
    """q: (B, Hq, S, dh); k/v: (B, Hkv, S, dh) — causal flash attention."""
    check_backend(interpret)
    S = q.shape[2]
    bq, bk = min(block_q, S), min(block_k, S)
    pad = (-S) % bq
    if pad:
        z = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = jnp.pad(q, z), jnp.pad(k, z), jnp.pad(v, z)
    out = flash_attention_fwd(
        q, k, v, window=window, block_q=bq, block_k=bk, interpret=interpret
    )
    return out[:, :, :S] if pad else out


def flash_attention(q, k, v, *, window=None, interpret: bool = False):
    """Model-layout entry: q (B, S, Hkv, G, dh); k/v (B, S, Hkv, dh)."""
    B, S, Hkv, G, dh = q.shape
    qh = jnp.moveaxis(q.reshape(B, S, Hkv * G, dh), 1, 2)
    kh = jnp.moveaxis(k, 1, 2)
    vh = jnp.moveaxis(v, 1, 2)
    out = flash_attention_bhsd(qh, kh, vh, window=window, interpret=interpret)
    return jnp.moveaxis(out, 2, 1).reshape(B, S, Hkv, G, dh)
