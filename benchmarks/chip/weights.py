"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program is handed them,
and the plain reference draws the same values again from the seed, layer by
layer, so that it takes nothing the program has made.

The tree is the family's `layout(cfg)` (`families/<name>.py`), the parameter
tree the program reads, as {path: (shape, dtype name)}; the harness refuses to
run when the program's own tree differs from it. Every leaf is drawn from its
own key, and each layer of a stacked leaf (`layers/...`) from a key of its
own, so that one layer can be drawn alone.

Leaves are drawn per layer, without the stacking axis. A leaf of rank 2 or
more is a matrix, or a stack of matrices such as a layer's experts (E, d_in,
d_out): it is drawn at std 1/sqrt(`shape[-2]`), its input axis behind any
leading axes, and each leading index (each expert) is a matrix drawn in its
own right, from a key of its own. A rank-1 leaf that is no norm scale or bias
keeps std 1/sqrt(`shape[0]`). `leaf` and `layer_leaf` give the same values
for the same layer.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# per-leaf distributions: matrices ~ U with std 1/sqrt(fan_in) (unit-scale
# outputs at any width); the embedding at std 1; norm scales at 1 +- 0.1 and
# biases at std 0.1, so that a norm that drops its scale or bias shows
NORM_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits. `jax.random.key(seed)`
    keeps only the low 32 bits when 64-bit mode is off, so 2**32 + 5 and 5
    would give the same weights."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(key, path: str, shape: tuple[int, ...], tied: bool = False) -> jax.Array:
    """One matrix, vector or stack of matrices of `path` (without its layer
    axis), in float32. A tied embedding is the head too, and takes the head's
    std 1/sqrt(d_model): at std 1 its logits would favour the current token by
    far."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        std, mean = NORM_STD, 1.0
    elif name == "bias":
        std, mean = NORM_STD, 0.0
    elif path == "embed/tok":
        std, mean = (shape[1] ** -0.5 if tied else 1.0), 0.0
    elif len(shape) > 2:  # a stack of matrices: each from a key of its own
        index = jnp.arange(shape[0], dtype=jnp.uint32)
        return jax.vmap(lambda i: draw(jax.random.fold_in(key, i), path, shape[1:]))(index)
    else:
        std, mean = shape[0] ** -0.5, 0.0
    half = std * 3.0**0.5
    return mean + jax.random.uniform(key, shape, jnp.float32, -half, half)


def layer_leaf(key, path: str, shape: tuple[int, ...], dtype: str, layer) -> jax.Array:
    """Layer `layer` of a stacked leaf, rounded to its stored dtype, in float32."""
    k = jax.random.fold_in(_leaf_key(key, path), layer)
    return draw(k, path, shape).astype(dtype).astype(jnp.float32)


def leaf(key, path: str, shape: tuple[int, ...], dtype: str, tied: bool = False) -> jax.Array:
    """A whole leaf in its stored dtype; stacked leaves layer by layer."""
    if path.startswith("layers/"):
        k = _leaf_key(key, path)
        layers = jnp.arange(shape[0], dtype=jnp.uint32)
        per = jax.vmap(lambda i: draw(jax.random.fold_in(k, i), path, shape[1:]))(layers)
        return per.astype(dtype)
    return draw(_leaf_key(key, path), path, shape, tied).astype(dtype)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return tree


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        out.update(flatten(v, path) if isinstance(v, dict) else {path: v})
    return out


def build(family, cfg: dict, key) -> dict:
    """The parameter tree of `cfg` for `key` (traceable)."""
    tied = cfg["tie_embeddings"]
    return nest({p: leaf(key, p, *spec, tied) for p, spec in family.layout(cfg).items()})


def make(family, cfg: dict, seed: int) -> dict:
    """The parameter tree of `cfg` for `seed`, in one jitted call on the device;
    the key is an argument, so one compiled program serves every seed."""
    return jax.jit(lambda key: build(family, cfg, key))(seed_key(seed))


def check_tree(family, cfg: dict, shapes) -> None:
    """Raise unless the program's parameter tree (ShapeDtypeStructs) is the
    family's `layout`."""
    got = {p: (tuple(s.shape), str(s.dtype)) for p, s in flatten(shapes).items()}
    want = family.layout(cfg)
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise RuntimeError(f"the program's parameter tree is not the benchmark's layout: {diff}")
