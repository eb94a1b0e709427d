"""What `ServeEngine` and the model step leave in a profiler trace.

The engine's host spans (`engine.*`) nest as the chip benchmark reads them and
come once per decode step; its host counters describe the last `serve()`
call; its programs lower as `jit_prefill` and `jit_decode` and carry the
model's named scopes, which change the compiled programs' metadata and
nothing else.
"""

import contextlib
import dataclasses
import glob
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import Model
from repro.serve.engine import Request, ServeEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip"))
import scopes  # noqa: E402  (the chip benchmark's reduction, which reads the names)

# JAX compile-heavy: excluded from the fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow

# starcoder2-3b at tiny widths, in its own dtypes
TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
            vocab_size=256)
SLOTS, PROMPT, MAX_LEN = 2, 16, 64
SCOPES = ("embed", "norm", "attn/qkv", "attn/kv_write", "attn/core", "attn/out", "mlp", "head")
# each span and the one it must lie in
PARENT = {"engine.prefill": "engine.admit", "engine.splice": "engine.admit",
          "engine.dispatch": "engine.decode", "engine.fetch": "engine.decode"}


@pytest.fixture(scope="module")
def model():
    return Model(dataclasses.replace(get_config("starcoder2-3b"), **TINY))


@pytest.fixture(scope="module")
def engine(model):
    return ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_len=MAX_LEN, slots=SLOTS)


def requests(n, new):
    rng = np.random.default_rng(n)
    return [Request(prompt=rng.integers(0, TINY["vocab_size"], PROMPT).astype(np.int32),
                    max_new_tokens=new) for _ in range(n)]


def engine_spans(trace_dir):
    """(name, start_ns, end_ns) of every `engine.*` event in the trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes for line in plane.lines
            for ev in line.events if ev.name.startswith("engine.")]


def test_serve_writes_nested_engine_spans(engine, tmp_path):
    engine.serve(requests(SLOTS, 2))  # compiled before the trace
    # three requests on two slots: the third is admitted, and spliced, later
    with jax.profiler.trace(str(tmp_path)):
        engine.serve(requests(SLOTS + 1, 4))
    spans = engine_spans(str(tmp_path))
    names = [n for n, _, _ in spans]
    assert set(names) == {"engine.admit", "engine.prefill", "engine.splice", "engine.sample",
                          "engine.decode", "engine.dispatch", "engine.fetch"}
    assert names.count("engine.decode") == engine.decode_steps == 2 * 3
    assert names.count("engine.dispatch") == names.count("engine.fetch") == engine.decode_steps
    assert names.count("engine.admit") == names.count("engine.prefill") == 2
    assert names.count("engine.splice") == 1
    # one sample a round: each decode step's, and the last of each admission
    assert names.count("engine.sample") == engine.decode_steps + 2
    for name, s, e in spans:
        if name in PARENT:
            assert any(n == PARENT[name] and ps <= s and e <= pe for n, ps, pe in spans), name
        else:  # the outer spans lie in no other engine span
            assert not any(ps < s and e < pe for _, ps, pe in spans), name


def test_counters_describe_the_last_call(engine):
    engine.serve(requests(SLOTS, 6))
    assert engine.decode_steps == 5
    assert 0 < engine.dispatch_s <= engine.decode_s
    assert engine.sample_s > 0 and engine.prefill_s > 0
    engine.serve(requests(SLOTS, 1))  # one token each: no decode step
    assert engine.decode_steps == 0
    assert engine.decode_s == engine.dispatch_s == 0.0
    assert engine.sample_s > 0
    engine.serve([])
    assert (engine.prefill_s, engine.decode_s, engine.sample_s, engine.dispatch_s) == (0, 0, 0, 0)


def compiled_texts(model):
    eng = ServeEngine(model, None, max_len=MAX_LEN, slots=SLOTS)
    return scopes.compiled_texts(eng, model, {"slots": SLOTS, "prompt_len": PROMPT,
                                              "max_len": MAX_LEN})


def canonical(text):
    """The program without its debug information (each instruction's
    `metadata={...}` and the tables of source files, functions, locations and
    stack frames that it points into) and with every `%name` numbered in the
    order it first appears: two programs that read the same are op for op the
    same, apart from names."""
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n", "", text,
                  flags=re.S | re.M)
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    names: dict = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m.group(), f"%{len(names)}"), text)


def test_programs_are_named_and_scoped_and_otherwise_unchanged(model, monkeypatch):
    texts = compiled_texts(model)
    smap = scopes.scope_map(texts)
    assert set(smap) == {"jit_prefill", "jit_decode"}
    for program, names in smap.items():
        assert set(SCOPES) <= set(names.values()), program

    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = compiled_texts(model)
    assert set(scopes.scope_map(bare)["jit_decode"].values()) == {scopes.UNSCOPED}
    for scoped, plain in zip(texts, bare):
        assert canonical(scoped) == canonical(plain)
