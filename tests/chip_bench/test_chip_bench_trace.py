"""The reduction from a profiler trace to busy, idle, collective and top-op
seconds: its interval arithmetic, a trace with no annotated window, and a
trace recorded on the chip."""

from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_subtract():
    merged = devtrace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert devtrace.length(merged) == 7
    # time in [0,3] u [5,9] not covered by [1,2] u [6,7] u [8,20]
    assert devtrace.subtract(merged, [[1, 2], [6, 7], [8, 20]]) == 1 + 1 + 1 + 1


def test_no_annotated_window_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert devtrace.reduce_xplane(devtrace.find_xplane(str(tmp_path))) is None


def test_busy_idle_collectives_and_gap_labels():
    ms = 1_000_000
    host = [("wave", 0, 100 * ms), ("PjitFunction(decode)", 40 * ms, 60 * ms),
            ("np.array", 45 * ms, 55 * ms), ("before the window", -50 * ms, 0)]
    devices = [
        ("/device:TPU:0", [("fusion.1", -10 * ms, 30 * ms), ("all-reduce.2", 20 * ms, 40 * ms),
                           ("fusion.1", 70 * ms, 90 * ms)]),
        ("/device:TPU:1", [("fusion.1", 0, 100 * ms)]),
    ]
    got = devtrace.reduce_events(host, devices)
    assert got["window_s"] == pytest.approx(0.1)
    # TPU:0 busy 0-40 and 70-90 ms, TPU:1 all 100 ms
    assert got["busy_s"] == pytest.approx((0.06 + 0.1) / 2)
    # the all-reduce runs alone from 30 to 40 ms on TPU:0
    assert got["collective_only_s"] == pytest.approx(0.01 / 2)
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.03 + 0.02 + 0.1)]
    # TPU:0's gaps: 40-70 ms (middle under np.array) and 90-100 ms (the wave)
    assert dict(got["idle_gaps"]) == {"np.array": pytest.approx(0.015),
                                      "wave": pytest.approx(0.005)}
    assert devtrace.reduce_events([("other", 0, 1)], devices) is None



def test_nested_operations_count_once():
    ms = 1_000_000
    host = [("wave", 0, 100 * ms)]
    devices = [("/device:TPU:0", [
        ("%while.2 = (s32[]) while(...)", 0, 60 * ms),
        ("%fusion.7 = bf16[8] fusion(...)", 0, 20 * ms),
        ("%fusion.8 = bf16[8] fusion(...)", 25 * ms, 60 * ms),
        ("%copy.3 = bf16[8] copy(...)", 50 * ms, 80 * ms),  # overlaps, not inside
    ])]
    got = devtrace.reduce_events(host, devices)
    assert got["busy_s"] == pytest.approx(0.08)
    assert dict(got["device_ops"]) == {"%fusion.7": pytest.approx(0.02),
                                       "%fusion.8": pytest.approx(0.035),
                                       "%copy.3": pytest.approx(0.03)}


def test_trace_recorded_on_the_chip():
    """`data/tpu_v5e_waves.xplane.pb`, recorded by `record_trace.py` on one TPU
    v5e: two waves of eight 2048^3 bf16 products, each wave ending in a 20 ms
    host sleep. The device's clock in that trace runs about 1.5 ms ahead of the
    host's, so the first products of the first wave fall before the window."""
    got = devtrace.reduce_xplane(str(DATA / "tpu_v5e_waves.xplane.pb"))
    assert [d["device"] for d in got["devices"]] == ["/device:TPU:0"]
    assert got["window_s"] == pytest.approx(0.045783805)
    assert got["busy_s"] == pytest.approx(0.001135985)
    assert got["collective_only_s"] == 0.0
    ops = dict(got["device_ops"])
    assert max(ops, key=ops.get) == "%convolution_tanh_fusion"
    # at most 16 products of 17.2 GFLOP each, at no more than the 197 TFLOP/s peak
    assert 11 * 17.18e9 / 197e12 <= ops["%convolution_tanh_fusion"] <= 16 * 17.18e9 / 197e12 * 1.2
    label, idle = got["idle_gaps"][0]
    assert "sleep" in label and idle == pytest.approx(0.044048964)
    assert got["busy_s"] + sum(s for _, s in got["idle_gaps"]) == pytest.approx(got["window_s"])
