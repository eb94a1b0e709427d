"""Single-source-of-truth parameter definitions.

Model code builds a pytree of `PD` descriptors (shape + logical sharding +
initializer). The same tree is consumed twice:
  * `init_params`  — materialize arrays (per-leaf folded keys, deterministic),
  * `param_specs`  — the matching pytree of PartitionSpec for pjit shardings.

This guarantees the sharding tree can never drift from the parameter tree.
"""

from __future__ import annotations

import functools
import math
import zlib
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.axes import logical_spec


@dataclass(frozen=True)
class PD:
    """Parameter definition: shape, logical axes per dim, initializer."""

    shape: tuple[int, ...]
    logical: tuple[Any, ...]  # one logical name (or None / tuple) per dim
    init: str = "normal"  # normal | zeros | ones | constant
    stddev: float = 0.02
    constant: float = 0.0
    dtype: Any = None  # override param dtype (e.g. fp32 for norms/states)

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def stacked(pd: PD, n: int) -> PD:
    """Add a leading layer-stack axis (unsharded) for scan-over-layers."""
    return PD(
        shape=(n, *pd.shape),
        logical=(None, *pd.logical),
        init=pd.init,
        stddev=pd.stddev,
        constant=pd.constant,
        dtype=pd.dtype,
    )


@functools.partial(jax.jit, static_argnums=(0, 2))
def _materialize(pd: PD, key, default_dtype) -> jax.Array:
    # jitted per leaf so a random leaf is drawn straight into its dtype: an
    # eager draw would hold float32 temporaries of a whole layer stack (several
    # GB for a 30-layer MLP leaf) beside the parameters already on the device
    dtype = pd.dtype or default_dtype
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, dtype)
    if pd.init == "ones":
        return jnp.ones(pd.shape, dtype)
    if pd.init == "constant":
        return jnp.full(pd.shape, pd.constant, dtype)
    # random leaves are drawn as a matrix and reshaped: the same values, and
    # for a described TPU v5e starcoder2-3b's 7 random leaves compile in
    # 31.5-32.9 s this way against 53.9-56.1 s drawn at their 3-D stack shape
    mat = (math.prod(pd.shape[:-1]), pd.shape[-1]) if len(pd.shape) > 2 else pd.shape
    if pd.init == "normal":
        x = jax.random.normal(key, mat, jnp.float32) * pd.stddev
    elif pd.init == "uniform":  # U(-c, c)
        x = jax.random.uniform(key, mat, jnp.float32, -pd.constant, pd.constant)
    else:
        raise ValueError(f"unknown init {pd.init}")
    return x.astype(dtype).reshape(pd.shape)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def init_params(defs, key, default_dtype=jnp.float32):
    """Materialize a PD tree into a parameter pytree (deterministic per path)."""

    def make(path, pd: PD):
        # crc32, not hash(): python string hashing is salted per-process and
        # would break cross-process determinism of initialization.
        leaf_key = jax.random.fold_in(key, zlib.crc32(_path_str(path).encode()) & 0x7FFFFFFF)
        return _materialize(pd, leaf_key, default_dtype)

    return jax.tree_util.tree_map_with_path(make, defs, is_leaf=lambda x: isinstance(x, PD))


def param_specs(defs):
    """PartitionSpec pytree matching a PD tree (resolved via current rules)."""
    return jax.tree.map(
        lambda pd: logical_spec(*pd.logical), defs, is_leaf=lambda x: isinstance(x, PD)
    )


def param_shapes(defs, default_dtype=jnp.float32):
    """ShapeDtypeStruct pytree for AOT lowering without allocation."""
    return jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype or default_dtype),
        defs,
        is_leaf=lambda x: isinstance(x, PD),
    )


def count_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, PD))
    total = 0
    for pd in leaves:
        n = 1
        for s in pd.shape:
            n *= s
        total += n
    return total
