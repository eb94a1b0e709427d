"""The correctness check fails what it must: a run of a tiny cell with the
timed path broken underneath reads `correct` false, once for each fault a
serving cell can have, and the float8 control fails where sound runs pass."""

import jax
import jax.numpy as jnp
import pytest
from bench_fixtures import TINY_LIMITS, check_fault, control_readings


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
