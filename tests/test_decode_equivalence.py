"""The serving invariant: prefill-then-decode must reproduce the full forward
pass token-for-token, for every architecture family (attention KV caches,
SWA ring buffers, Mamba2 recurrent state, RWKV6 wkv state, MoE routing)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.transformer import Model
from repro.serve.engine import Request, ServeEngine

# JAX compile-heavy: excluded from the fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow

CASES = [
    "qwen3_14b",  # GQA + qk_norm
    "h2o_danube_1p8b",  # SWA ring buffer
    "rwkv6_7b",  # wkv state
    "zamba2_1p2b",  # mamba2 + shared attn
    "musicgen_large",  # MHA
    "starcoder2_3b",  # LayerNorm, tanh-GELU, tied head
    "minitron_8b",  # LayerNorm, squared ReLU, untied head
]


def _full_logits(m, params, batch):
    x, _ = m.forward(params, batch)
    return np.asarray(m._head(params, x))


@pytest.mark.parametrize("arch", CASES)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if arch == "h2o_danube_1p8b":
        cfg = dataclasses.replace(cfg, sliding_window=16)
    m = Model(cfg)
    key = jax.random.PRNGKey(1)
    B, S, S0 = 2, 48, 32
    params = m.init(key)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    ref = _full_logits(m, params, {"tokens": tokens})

    logits, cache = jax.jit(lambda p, b: m.prefill(p, b, max_len=S))(
        params, {"tokens": tokens[:, :S0]}
    )
    errs = [np.abs(np.asarray(logits) - ref[:, S0 - 1]).max()]
    dec = jax.jit(lambda p, c, t: m.decode_step(p, c, t))
    for t in range(S0, S):
        logits, cache = dec(params, cache, tokens[:, t : t + 1])
        errs.append(np.abs(np.asarray(logits) - ref[:, t]).max())
    assert max(errs) < 2e-3, (arch, max(errs))


def test_moe_prefill_decode_dropless():
    """With dropless capacity, MoE decode must match the full pass exactly;
    with tight capacity they may differ (token-priority dropping is
    batch-dependent) — both behaviours are asserted."""
    base = get_config("deepseek_moe_16b").reduced()
    cfg = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, capacity_factor=float(base.moe.num_experts))
    )
    m = Model(cfg)
    key = jax.random.PRNGKey(1)
    B, S, S0 = 2, 48, 32
    params = m.init(key)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    ref = _full_logits(m, params, {"tokens": tokens})
    logits, cache = jax.jit(lambda p, b: m.prefill(p, b, max_len=S))(
        params, {"tokens": tokens[:, :S0]}
    )
    errs = [np.abs(np.asarray(logits) - ref[:, S0 - 1]).max()]
    dec = jax.jit(lambda p, c, t: m.decode_step(p, c, t))
    for t in range(S0, S):
        logits, cache = dec(params, cache, tokens[:, t : t + 1])
        errs.append(np.abs(np.asarray(logits) - ref[:, t]).max())
    assert max(errs) < 2e-3, max(errs)


@pytest.mark.parametrize("arch", ["starcoder2_3b", "deepseek_moe_16b"])
def test_decode_scan_carries_stacked_cache(arch):
    """The layer scan carries the stacked K and V and updates them in place:
    taken as scan inputs and outputs they are sliced and rewritten whole."""
    m = Model(get_config(arch).reduced())
    B, max_len = 2, 64
    cache = m.cache_shapes(B, max_len)
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(m.decode_step)(m.pshapes(), cache, tokens).jaxpr
    stacked = cache["layers"]["kv"]["k"].shape
    assert stacked[0] == m.n_scan()
    scans = [e for e in jaxpr.eqns
             if e.primitive.name == "scan" and e.params["length"] == m.n_scan()]
    assert len(scans) == 1
    n_carry = scans[0].params["num_carry"]
    shapes = [v.aval.shape for v in scans[0].outvars]
    assert shapes[:n_carry].count(stacked) == 2  # K and V
    assert stacked not in shapes[n_carry:]


def test_serve_splice_matches_generate():
    """`serve` refills a freed slot while another decodes on: the new prompt's
    cache is spliced in along the KV cache's batch axis (neither leading nor
    second) and both requests get the tokens `generate` gives them."""
    cfg = get_config("starcoder2_3b").reduced()
    m = Model(cfg)
    eng = ServeEngine(m, m.init(jax.random.PRNGKey(3)), max_len=32, slots=2)
    rng = np.random.default_rng(3)
    p0, p1 = (rng.integers(0, cfg.vocab_size, 8).astype(np.int32) for _ in range(2))
    # slot 1 frees after 2 tokens, when the shared position has reached 10:
    # a 10-token prompt then joins at the position its own prefill gives it
    p2 = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    reqs = [Request(p0, 6), Request(p1, 2), Request(p2, 4)]
    eng.serve(reqs)
    assert eng.decode_steps == 5
    first = eng.generate([p0, p1], 6)
    second = eng.generate([p2, p2], 4)
    assert reqs[0].out_tokens == first[0]
    assert reqs[1].out_tokens == first[1][:2]
    assert reqs[2].out_tokens == second[0]
