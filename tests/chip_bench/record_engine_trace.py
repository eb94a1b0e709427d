"""Record the small engine trace that `test_chip_bench_scopes.py` reduces.

  python tests/chip_bench/record_engine_trace.py tests/chip_bench/data/tpu_v5e_engine

Writes `<out>.xplane.pb.gz`, a profiler trace of one `wave` annotation, as
the harness makes it, around a `ServeEngine.serve` of two decode steps of a
2-layer starcoder2-3b at reduced widths, and `<out>.hlo.txt.gz`, the compiled
HLO text of the engine's two programs (`scopes.compiled_texts`); both compressed, the trace without its plane of
HLO protos. Run it on the chip; off a TPU it refuses (exit 3).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks" / "chip")]

# starcoder2-3b cut to 2 layers and narrow widths; 4 slots of 64-token prompts
SIZES = dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=2, head_dim=64, d_ff=1024,
             vocab_size=1024)
MIX = {"slots": 4, "prompt_len": 64, "max_len": 128}
NEW_TOKENS = 3  # the first from prefill, then two decode steps


def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes):
    """(field number, value if length-delimited, start, end) of each field of a
    serialized protocol buffer message."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        wire, value = key & 7, None
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 1:
            i += 8
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = b[i:i + n], i + n
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value, start, i


def without_plane(xspace: bytes, name: str) -> bytes:
    """A serialized XSpace without the plane called `name` (an XSpace's field 1
    is a plane, a plane's field 2 its name)."""
    def named(plane):
        return any(f == 2 and v == name.encode() for f, v, _, _ in _fields(plane))

    return b"".join(xspace[s:e] for f, v, s, e in _fields(xspace) if not (f == 1 and named(v)))


def main(out: str) -> int:
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    import scopes
    from repro.configs import get_config
    from repro.models.transformer import Model
    from repro.serve.engine import Request, ServeEngine

    model = Model(dataclasses.replace(get_config("starcoder2-3b"), **SIZES))
    engine = ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_len=MIX["max_len"],
                         slots=MIX["slots"])
    rng = np.random.default_rng(0)

    def wave():
        return [Request(prompt=rng.integers(0, SIZES["vocab_size"], MIX["prompt_len"])
                        .astype(np.int32), max_new_tokens=NEW_TOKENS)
                for _ in range(MIX["slots"])]

    engine.serve(wave())  # compiled before the trace
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("wave"):
            engine.serve(wave())
        jax.profiler.stop_trace()
        (found,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        # `/host:metadata` holds the programs' HLO protos, three fifths of the
        # file, and nothing the reductions read
        with gzip.open(out + ".xplane.pb.gz", "wb") as f:
            f.write(without_plane(Path(found).read_bytes(), "/host:metadata"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    with gzip.open(out + ".hlo.txt.gz", "wt") as f:
        f.write("\n".join(scopes.compiled_texts(engine, model, MIX)))
    for suffix in (".xplane.pb.gz", ".hlo.txt.gz"):
        print(f"{out}{suffix}: {os.path.getsize(out + suffix)} bytes")
    print(f"decode steps {engine.decode_steps}, device {jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
