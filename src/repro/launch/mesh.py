"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax

from repro.configs.base import ShapeSpec
from repro.parallel.axes import ShardingRules, make_rules


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (data, model); multi-pod adds a leading 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh over the first prod(shape) devices, every axis `Auto`.

    The model relies on GSPMD propagation (gathers, reshapes under sharding
    constraints); `Explicit` axes, `jax.make_mesh`'s default since JAX 0.9,
    reject those ops. The dry-run process exposes 512 host devices and the
    single-pod mesh uses the first 256.
    """
    import math

    from jax.sharding import AxisType

    n = math.prod(shape)
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(f"mesh {shape} needs {n} devices, have {len(devices)}")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def rules_for(mesh, shape: ShapeSpec | None = None, *, sequence_parallel: bool = True,
              zero1: bool = True) -> ShardingRules:
    """Default logical->mesh axis rules for a production mesh.

    Batch shards over ("pod", "data"); weights over "model". For decode shapes
    whose global batch is smaller than the dp axes (long-context B=1), the
    data axis is repurposed for context parallelism over the KV/seq dim.
    """
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    tp = ("model",) if "model" in names else ()
    cp: tuple[str, ...] = ()
    if shape is not None and shape.kind == "decode":
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if shape.global_batch < dp_size:
            cp = tuple(a for a in ("data",) if a in names)
            dp = tuple(a for a in ("pod",) if a in names)
            if shape.global_batch == 1:
                dp = ()
    return make_rules(dp=dp, tp=tp, sequence_parallel=sequence_parallel,
                      context_parallel=cp, zero1=zero1)
