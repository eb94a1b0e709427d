"""Shared layers: norms, rotary embeddings, activations, embedding/lm-head."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import PD
from repro.parallel.axes import shard


# ---------------------------------------------------------------- activations
def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":  # squared ReLU (Nemotron/minitron, RWKV channel-mix)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


# ---------------------------------------------------------------------- norms
def norm_defs(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": PD((d,), (None,), init="ones", dtype=jnp.float32)}
    if kind == "layernorm":
        return {
            "scale": PD((d,), (None,), init="ones", dtype=jnp.float32),
            "bias": PD((d,), (None,), init="zeros", dtype=jnp.float32),
        }
    raise ValueError(kind)


def apply_norm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm / LayerNorm in fp32, cast back to input dtype."""
    with jax.named_scope("norm"):
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        if "bias" in p:  # layernorm
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.var(xf, axis=-1, keepdims=True)
            y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
        else:  # rmsnorm
            ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
        return y.astype(dtype)


def rms_head_norm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMSNorm over the trailing head_dim (qwen3 qk_norm)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(dtype)


def group_norm_heads(p: dict, x: jax.Array, eps: float = 64e-5) -> jax.Array:
    """Per-head GroupNorm over head_dim (RWKV ln_x). x: (..., H, dh)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    h, dh = x.shape[-2], x.shape[-1]
    y = y * p["scale"].reshape(h, dh) + p["bias"].reshape(h, dh)
    return y.astype(dtype)


# ----------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, dh); positions: (S,) or (B, S) absolute token positions.

    Uses the half-split convention (rotate_half), matching Llama-family models.
    Odd head_dims (none assigned) are unsupported by construction.
    """
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, dh/2)
        ang = ang[None, :, None, :]  # (1, S, 1, dh/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- embeddings
def embed_defs(vocab: int, d: int) -> dict:
    # vocab-sharded over tp (Megatron-style embedding parallelism)
    return {"tok": PD((vocab, d), ("tp", None), init="normal", stddev=0.02)}


def embed_lookup(p: dict, tokens: jax.Array, dtype) -> jax.Array:
    with jax.named_scope("embed"):
        emb = p["tok"].astype(dtype)
        x = jnp.take(emb, tokens, axis=0)
        return shard(x, "dp", "sp", None)


def head_defs(d: int, vocab: int) -> dict:
    return {"w": PD((d, vocab), (None, "tp"), init="normal", stddev=0.02)}


def lm_logits(p: dict, x: jax.Array, dtype) -> jax.Array:
    """x: (..., d) -> (..., vocab), vocab-sharded."""
    w = p["w"].astype(dtype)
    logits = x @ w
    return shard(logits, "dp", None, "tp") if logits.ndim == 3 else shard(logits, "dp", "tp")
