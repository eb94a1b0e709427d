"""What the plain references of every family share: float32 matrix products
at `jax.default_matmul_precision("highest")`, their float8 control, norms,
activations, rotary positions and causal attention in blocks of query rows.

Each family (`families/<name>.py`) builds its own reference from these. A
reference imports nothing of the program and draws its weights from the seed
itself (`weights.py`), layer by layer.

`mode="fp8"` is the control: every matrix product takes its operands rounded
to float8 e4m3 with one scale per tensor, the precision below the bfloat16 the
configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import family

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128  # query rows per attention block
FP8_MAX = 448.0


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def einsum(spec, a, b, mode):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown reference mode {mode!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def layer_norm(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def act(name, x):
    if name == "gelu":  # tanh approximation, as starcoder2's gelu_pytorch_tanh
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))
    if name == "relu2":
        return jnp.maximum(x, 0.0) ** 2
    raise ValueError(f"the reference has no activation {name!r}")


def rope(x, theta):
    """x: (N, T, H, dh) at positions 0..T-1; pairs (i, i + dh/2) rotate."""
    T, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg, h, wq, wk, wv, wo, mode):
    """Grouped-query causal attention with rotary positions over `h` (N, T, d),
    with the projections `wq`, `wk`, `wv` and the output `wo`."""
    N, T, _ = h.shape
    Hq, Hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = rope(einsum("ntd,de->nte", h, wq, mode).reshape(N, T, Hq, dh), cfg["rope_theta"])
    k = rope(einsum("ntd,de->nte", h, wk, mode).reshape(N, T, Hkv, dh), cfg["rope_theta"])
    v = einsum("ntd,de->nte", h, wv, mode).reshape(N, T, Hkv, dh)
    q = q.reshape(N, T, Hkv, Hq // Hkv, dh)
    B = min(Q_BLOCK, T)
    nb = -(-T // B)
    q = jnp.pad(q, ((0, 0), (0, nb * B - T), (0, 0), (0, 0), (0, 0)))
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * B, B, axis=1)
        s = einsum("nqhgd,nkhd->nhgqk", qb, k, mode) * dh**-0.5
        qpos = i * B + jnp.arange(B)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return einsum("nhgqk,nkhd->nqhgd", w, v, mode)

    o = jax.lax.map(block, jnp.arange(nb))  # (nb, N, B, Hkv, G, dh)
    o = jnp.moveaxis(o, 0, 1).reshape(N, nb * B, Hq * dh)[:, :T]
    return einsum("nte,ed->ntd", o, wo, mode)


def static(cfg: dict) -> tuple:
    """The numbers and names of a configuration's dict, hashable: a jitted
    reference takes them as a static argument. Those of a program-config
    section (`family.sections`) come under dotted names, `"moe.top_k"`."""
    items = [(k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))]
    items += [(f"{name}.{k}", v) for name, given in family.sections(cfg).items()
              for k, v in given.items() if isinstance(v, (int, float, str))]
    return tuple(sorted(items))
