"""Record the small profiler trace that `test_chip_bench_trace.py` reduces.

  python tests/chip_bench/record_trace.py tests/chip_bench/data/tpu_v5e_waves.xplane.pb

Two `wave` annotations, as the harness makes them, each holding a chain of
bf16 matrix products and then a host wait (`host_wait`) of `WAIT_S` during
which the device has nothing to do. Run it on the chip; off a TPU it refuses
(exit 3).
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

WAVES, PRODUCTS, N, WAIT_S = 2, 8, 2048, 0.02


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    step = jax.jit(lambda x, w: jnp.tanh(x @ w))
    x = jnp.ones((N, N), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(0), (N, N), jnp.bfloat16) * N**-0.5
    step(x, w).block_until_ready()  # compiled before the trace
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(WAVES):
            with jax.profiler.TraceAnnotation("wave"):
                y = x
                for _ in range(PRODUCTS):
                    y = step(y, w)
                y.block_until_ready()
                with jax.profiler.TraceAnnotation("host_wait"):
                    time.sleep(WAIT_S)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copyfile(found[0], out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes, device {jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
