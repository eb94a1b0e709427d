"""MLP block: gated (SiLU/GELU) or plain, Megatron column->row partitioned."""

from __future__ import annotations

import jax

from repro.models.layers import activation
from repro.models.params import PD
from repro.parallel.axes import shard


def mlp_defs(d: int, d_ff: int, gated: bool) -> dict:
    s = 0.02
    defs = {
        "wi": PD((d, d_ff), (None, "tp"), stddev=s),  # column-parallel
        "wo": PD((d_ff, d), ("tp", None), stddev=s),  # row-parallel
    }
    if gated:
        defs["wg"] = PD((d, d_ff), (None, "tp"), stddev=s)
    return defs


def apply_mlp(p: dict, x: jax.Array, act_name: str) -> jax.Array:
    """(B, S, D) or (T, D) -> same rank. One logical all-reduce after wo."""
    with jax.named_scope("mlp"):
        act = activation(act_name)
        dt = x.dtype
        h = x @ p["wi"].astype(dt)
        if "wg" in p:
            h = act(x @ p["wg"].astype(dt)) * h
        else:
            h = act(h)
        if x.ndim == 3:
            h = shard(h, "dp", None, "tp")
            return shard(h @ p["wo"].astype(dt), "dp", "sp", None)
        h = shard(h, "dp", "tp")
        return shard(h @ p["wo"].astype(dt), "dp", None)
