"""The correctness check fails what it must: a run of a tiny cell with the
timed path broken underneath reads `correct` false, once for each fault a
serving cell can have, and the float8 control fails where sound runs pass."""

import json

import jax
import jax.numpy as jnp
import pytest
from bench_fixtures import COMPARE_ALL, TINY_LIMITS, check_fault, control_readings, write_bench


def alter_one_token(monkeypatch):
    """The third decode step's logits put another token first in slot 0."""
    from repro.serve.engine import ServeEngine

    init = ServeEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        decode, calls = self._decode, [0]

        def altered(p, c, t):
            logits, cache = decode(p, c, t)
            calls[0] += 1
            if calls[0] % 7 == 3:
                top = jnp.argmax(logits[0])
                logits = logits.at[0, (top + 1) % logits.shape[-1]].set(logits[0, top] + 1.0)
            return logits, cache

        self._decode = altered

    monkeypatch.setattr(ServeEngine, "__init__", patched)


def stale_cache(monkeypatch):
    """Each decode step returns the cache it was given."""
    from repro.serve.engine import ServeEngine

    init = ServeEngine.__init__

    def patched(self, model, *a, **k):
        init(self, model, *a, **k)
        self._decode = jax.jit(lambda p, c, t: (model.decode_step(p, c, t)[0], c))

    monkeypatch.setattr(ServeEngine, "__init__", patched)


@pytest.mark.parametrize("fault", [alter_one_token, stale_cache])
def test_fault_reads_incorrect(tiny, capsys, monkeypatch, fault):
    check_fault(tiny, capsys, monkeypatch, "tiny.waves", fault, "logit_gap")


def test_float8_control_fails_where_the_program_passes(tiny):
    limit = TINY_LIMITS["logit_gap"]["limit"]
    for row in control_readings(tiny, "tiny.waves", [21, 22, 23]):
        assert row["program"] <= limit < row["control"], row
        assert row["altered"] > limit and row["stale_cache"] > limit, row


# request counts at which a window's sample of 4 read the stale cache as 0.0
STALE_ZERO_COUNTS = [64, 100, 112, 292, 312, 400, 524, 624]


@pytest.fixture(scope="module")
def stale_served(tmp_path_factory):
    """The tiny cell's first 156 waves (624 requests) served with a stale
    cache, and what `serving.served_gap` needs to compare them."""
    import family
    import run
    import traffic
    import weights
    from repro.models.transformer import Model
    from repro.serve.engine import Request, ServeEngine

    base = write_bench(tmp_path_factory.mktemp("bench"))
    cfg = json.loads((base / "configs" / "tiny.json").read_text())
    fam = family.load(base, cfg)
    model = Model(run.model_config(cfg, fam))
    seed, m = 5, COMPARE_ALL
    with pytest.MonkeyPatch.context() as mp:
        stale_cache(mp)
        engine = ServeEngine(model, weights.make(fam, cfg, seed), max_len=m["max_len"],
                             slots=m["slots"])
    finished = []
    for w in range(max(STALE_ZERO_COUNTS) // m["slots"]):
        reqs = [Request(prompt=p, max_new_tokens=m["new_tokens"])
                for p in traffic.prompts(seed, w, m["slots"], m["prompt_len"], cfg["vocab_size"])]
        engine.serve(reqs)
        finished += [(r.prompt, list(r.out_tokens), r.done) for r in reqs]
    return fam, cfg, seed, finished


@pytest.mark.parametrize("count", STALE_ZERO_COUNTS)
def test_stale_cache_fails_at_every_request_count(stale_served, count):
    """What a run with the stale cache compares after a window of `count`
    requests: every finished request, as `check_fault` sets the tiny cell."""
    import serving

    fam, cfg, seed, finished = stale_served
    m = COMPARE_ALL
    gap = serving.served_gap(fam, cfg, seed, finished[:count], m["prompt_len"], m["new_tokens"],
                             m["check_requests"])
    assert gap > TINY_LIMITS["logit_gap"]["limit"], gap
