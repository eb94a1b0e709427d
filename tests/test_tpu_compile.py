"""Compile the main-path programs for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode and the CPU backend accept: block
shapes the tiling cannot take, kernels over their fast-memory budget, and
programs that do not fit the chip's 16 GiB. Each test compiles one program at
real widths for one chip of a `v5e:2x2` topology. Nothing runs.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
"""

import dataclasses
import functools
import importlib.util

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.decode_attention import flash_decode
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_fwd, rmsnorm_residual_fwd
from repro.models.transformer import Model
from repro.serve.engine import ServeEngine

# JAX compile-heavy: excluded from the fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow

HBM_BYTES = 16 * 2**30  # one TPU v5e chip

# the serving sizes chip_smoke.py runs: 8 slots, 1024-token prompts, max_len 2048
SLOTS, PROMPT, MAX_LEN = 8, 1024, 2048


@pytest.fixture(scope="module")
def one_chip():
    # only a missing TPU compiler skips; any error describing the chip fails
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) is installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)


# (Hq, Hkv, dh, window): starcoder2-3b, h2o-danube-1.8b
ATTN_WIDTHS = {"starcoder2-3b": (24, 2, 128, None), "h2o-danube-1.8b": (32, 8, 80, 4096)}


@pytest.mark.parametrize("arch", list(ATTN_WIDTHS))
def test_flash_attention_compiles(one_chip, arch):
    hq, hkv, dh, window = ATTN_WIDTHS[arch]
    S = 4096
    q = _spec((1, hq, S, dh), jnp.bfloat16, one_chip)
    kv = _spec((1, hkv, S, dh), jnp.bfloat16, one_chip)
    fn = jax.jit(functools.partial(flash_attention_fwd, window=window))
    assert "tpu_custom_call" in fn.lower(q, kv, kv).compile().as_text()


@pytest.mark.parametrize("arch", list(ATTN_WIDTHS))
def test_flash_decode_compiles(one_chip, arch):
    hq, hkv, dh, window = ATTN_WIDTHS[arch]
    T = window or MAX_LEN
    q = _spec((SLOTS, hkv, hq // hkv, dh), jnp.bfloat16, one_chip)
    kv = _spec((SLOTS, hkv, T, dh), jnp.bfloat16, one_chip)
    n_valid = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(flash_decode).lower(q, kv, kv, n_valid).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["starcoder2-3b", "h2o-danube-1.8b"])
def test_rmsnorm_compiles(one_chip, arch):
    D = get_config(arch).d_model
    x = _spec((SLOTS * PROMPT, D), jnp.bfloat16, one_chip)
    scale = _spec((D,), jnp.float32, one_chip)
    assert "tpu_custom_call" in jax.jit(rmsnorm_fwd).lower(x, scale).compile().as_text()
    fused = jax.jit(rmsnorm_residual_fwd).lower(x, x, scale).compile()
    assert "tpu_custom_call" in fused.as_text()


@pytest.fixture(scope="module")
def starcoder_engine():
    """The engine's own jitted programs for all 30 layers; params never built."""
    model = Model(get_config("starcoder2-3b"))
    return ServeEngine(model, None, max_len=MAX_LEN, slots=SLOTS)


def test_starcoder2_prefill_fits_one_chip(one_chip, starcoder_engine):
    eng = starcoder_engine
    params = _on(eng.model.pshapes(), one_chip)
    batch = {"tokens": _spec((SLOTS, PROMPT), jnp.int32, one_chip)}
    compiled = eng._prefill.lower(params, batch).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def test_starcoder2_decode_fits_one_chip(one_chip, starcoder_engine):
    eng = starcoder_engine
    params = _on(eng.model.pshapes(), one_chip)
    cache = _on(eng.model.cache_shapes(SLOTS, MAX_LEN), one_chip)
    tokens = _spec((SLOTS, 1), jnp.int32, one_chip)
    compiled = eng._decode.lower(params, cache, tokens).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def test_starcoder2_train_step_fits_one_chip(one_chip):
    """chip_smoke.py's training size: 4 layers at published widths, AdamW,
    selective remat, batch 2 x 4096."""
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.train.optimizer import adamw_init
    from repro.train.trainer import Trainer

    model = Model(dataclasses.replace(get_config("starcoder2-3b"), num_layers=4))
    trainer = Trainer(model, ParallelConfig(remat="selective"), TrainConfig(steps=5))
    pshapes = model.pshapes()
    state = _on({"params": pshapes,
                 "opt": jax.eval_shape(lambda p: adamw_init(p, trainer.tcfg), pshapes)},
                one_chip)
    batch = {k: _spec((2, 4096), jnp.int32, one_chip) for k in ("tokens", "labels")}
    compiled = trainer._step.lower(state, batch).compile()
    assert _total_bytes(compiled) < HBM_BYTES
