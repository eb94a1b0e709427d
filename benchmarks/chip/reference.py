"""Plain float32 reference of the dense decoder the benchmark's cells run.

Pre-norm blocks of LayerNorm -> grouped-query causal attention with rotary
positions (rotate-half convention) -> residual -> LayerNorm -> plain MLP (tanh
GELU or squared ReLU) -> residual; a final LayerNorm and the head, untied or
the embedding's transpose where `tie_embeddings` is set. It
imports nothing of the program and draws its weights from the seed itself
(`weights.py`), layer by layer. Every matrix product runs at
`jax.default_matmul_precision("highest")`; attention is computed in blocks of
query rows and the weights are drawn layer by layer, so that the reference
fits on one chip once the program's state is freed.

`mode="fp8"` is the control: every matrix product takes its operands rounded
to float8 e4m3 with one scale per tensor, the precision below the bfloat16 the
configurations state.

Departures from the published models are listed in each configuration file.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import weights

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


def attention(cfg, p, h, mode):
    N, T, _ = h.shape
    Hq, Hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = rope(einsum("ntd,de->nte", h, p["attn/wq"], mode).reshape(N, T, Hq, dh), cfg["rope_theta"])
    k = rope(einsum("ntd,de->nte", h, p["attn/wk"], mode).reshape(N, T, Hkv, dh), cfg["rope_theta"])
    v = einsum("ntd,de->nte", h, p["attn/wv"], mode).reshape(N, T, Hkv, dh)
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
    return einsum("nte,ed->ntd", o, p["attn/wo"], mode)


def block_fn(cfg, p, x, mode):
    x = x + attention(cfg, p, layer_norm(x, p["ln1/scale"], p["ln1/bias"]), mode)
    h = layer_norm(x, p["ln2/scale"], p["ln2/bias"])
    h = act(cfg["act"], einsum("ntd,df->ntf", h, p["mlp/wi"], mode))
    return x + einsum("ntf,fd->ntd", h, p["mlp/wo"], mode)


LAYER_NAMES = ("ln1/scale", "ln1/bias", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
               "ln2/scale", "ln2/bias", "mlp/wi", "mlp/wo")


# ------------------------------------------------------------------ serving
@partial(jax.jit, static_argnums=(0, 3, 4))
def _scored_logits(cfg_items, key, seqs, first, mode):
    cfg = dict(cfg_items)
    lay = weights.layout(cfg)
    emb = weights.leaf(key, "embed/tok", *lay["embed/tok"], cfg["tie_embeddings"])
    x = jnp.take(emb, seqs, axis=0).astype(jnp.float32)

    def body(x, i):
        p = {n: weights.layer_leaf(key, f"layers/{n}", lay[f"layers/{n}"][0][1:],
                                   lay[f"layers/{n}"][1], i) for n in LAYER_NAMES}
        return block_fn(cfg, p, x, mode), None

    x, _ = jax.lax.scan(body, x, jnp.arange(cfg["num_layers"], dtype=jnp.uint32))
    fn = {n: weights.leaf(key, f"final_norm/{n}", *lay[f"final_norm/{n}"]) for n in ("scale", "bias")}
    h = layer_norm(x[:, first:], fn["scale"], fn["bias"])
    if cfg["tie_embeddings"]:
        head = emb.T
    else:
        head = weights.leaf(key, "lm_head/w", *lay["lm_head/w"])
    return einsum("ntd,dv->ntv", h, head.astype(jnp.float32), mode)


def scored_logits(cfg: dict, seed: int, seqs, first: int, mode: str = "f32"):
    """Logits (N, T - first, V) at positions first..T-1 of the token rows
    `seqs` (N, T), each row read from position 0."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))))
    with jax.default_matmul_precision("highest"):
        return _scored_logits(items, weights.seed_key(seed), jnp.asarray(seqs), first, mode)
