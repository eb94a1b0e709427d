"""Operation, byte and parameter counts of the dense family against the sizes
worked out by hand, and the peak table's refusal of a chip it does not know."""

import json

import pytest
from bench_fixtures import HARNESS

import family
import flops
import peaks

dense = family.load(HARNESS, {"reference": "dense"})


def cfg(name):
    return json.loads((HARNESS / "configs" / f"{name}.json").read_text())


def test_parameters_of_the_configurations():
    # starcoder2-3b, head tied to the embedding: 30 x 95.9 M layer matrices + 151 M
    assert dense.params(cfg("starcoder2-3b")) == pytest.approx(3.03e9, rel=2e-3)
    assert dense.weight_bytes(cfg("starcoder2-3b")) == pytest.approx(6.06e9, rel=2e-3)
    # minitron-8b-l16, 48 heads of 128 over 4096: 16 x 192.9 M layer matrices
    # (6.17 GB) + embedding and untied head (4.19 GB)
    assert dense.layer_matmul_params(cfg("minitron-8b-l16")) == pytest.approx(192.9e6, rel=1e-3)
    assert dense.weight_bytes(cfg("minitron-8b-l16")) == pytest.approx(10.37e9, rel=2e-3)


def test_kv_bytes_per_token():
    assert dense.kv_bytes_per_token(cfg("starcoder2-3b")) == 30 * 1024
    assert dense.kv_bytes_per_token(cfg("minitron-8b-l16")) == 64 * 1024


def test_causal_attention_counts_half_the_square():
    c = cfg("starcoder2-3b")
    mm_only = dense.prefill_flops(c, 1, 1024) - dense.attention_pairs_flops(c) * 1024 * 1025 / 2
    assert mm_only == 2 * 1024 * c["num_layers"] * dense.layer_matmul_params(c) \
        + 2 * dense.head_params(c)
    # one decode step attends pos + 1 keys, whatever the cache's length
    step = dense.decode_flops(c, 1, 1023) - dense.decode_flops(c, 1, 1022)
    assert step == dense.attention_pairs_flops(c)


def test_decode_bytes_count_valid_positions_only():
    c = cfg("starcoder2-3b")
    kv = dense.kv_bytes_per_token(c)
    assert dense.decode_bytes(c, 16, 2047) - dense.decode_bytes(c, 16, 1023) == 16 * 1024 * kv


@pytest.mark.parametrize("name,untied_table", [("starcoder2-3b", 0), ("minitron-8b-l16", 1)])
def test_step_reads_every_weight_but_an_untied_embedding(name, untied_table):
    # a tied embedding is read whole by the head; an untied one only by the token's row
    c = cfg(name)
    weights_read = dense.decode_bytes(c, 1, 0) - dense.kv_bytes_per_token(c) - 2 * c["d_model"]
    assert weights_read == dense.weight_bytes(c) - untied_table * 2 * dense.head_params(c)


def test_wave_roofline_is_bound_by_the_slower_resource():
    c, p = cfg("starcoder2-3b"), peaks.peaks("TPU v5 lite")
    prefill = flops.wave_roofline_s(c, 16, 1024, 0, p)
    assert prefill == pytest.approx(dense.prefill_flops(c, 16, 1024) / p["bf16_flops"])
    step = flops.wave_roofline_s(c, 16, 1024, 1, p) - prefill
    assert step == pytest.approx(dense.decode_bytes(c, 16, 1024) / p["hbm_bytes_per_s"])


def test_peak_table_refuses_an_unknown_chip():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="TPU v4"):
        peaks.peaks("TPU v4")


@pytest.mark.parametrize("name,expect", [
    ("mfu.serve", 100.0),
    ("roofline.serve", 100.0),
    ("engine.prefill_ms", 300.0),
    ("engine.decode_step_ms", 1e3 / 127),
    ("engine.ttft_p95_ms", 300.0),
    ("engine.tpot_p95_ms", (2.0 - 0.3) / 127 * 1e3),
])
def test_host_clock_readers_leave_out_traced_waves(name, expect):
    # an untraced wave that runs exactly at the peak (mfu.serve), at the roofline
    # (roofline.serve), with a 0.3 s prefill and 1 s of decode (2 s in all
    # for the tail readers); the traced wave before it, twice as slow, must
    # not count
    import run

    c, p = cfg("starcoder2-3b"), peaks.peaks("TPU v5 lite")
    secs = {"mfu.serve": flops.wave_flops(c, 16, 1024, 127) / p["bf16_flops"],
            "roofline.serve": flops.wave_roofline_s(c, 16, 1024, 127, p)}.get(name, 2.0)
    wave = {"decode_steps": 127, "prefill_s": 0.3, "decode_s": 1.0, "tokens": [128] * 16}
    record = {"config": c, "peak": p, "chips": 1, "prompt_len": 1024, "slots": 16,
              "trace": None,
              "waves": [{**wave, "t0": 0.0, "t1": 2 * secs, "traced": True,
                         "prefill_s": 0.6, "decode_s": 2.0},
                        {**wave, "t0": 5.0, "t1": 5.0 + secs, "traced": False}]}
    assert run.load_reader(HARNESS, name)(record) == pytest.approx(expect)
    record["waves"] = record["waves"][:1]
    assert run.load_reader(HARNESS, name)(record) is None


def test_idle_share_needs_a_trace():
    import run

    record = {"trace": None, "waves": [{"t0": 0.0, "t1": 1.0, "traced": True}]}
    assert run.load_reader(HARNESS, "idle_share.serve")(record) is None
    record["trace"] = {"busy_s": 0.75, "window_s": 1.0}
    assert run.load_reader(HARNESS, "idle_share.serve")(record) == pytest.approx(25.0)
