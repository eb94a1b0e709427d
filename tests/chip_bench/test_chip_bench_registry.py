"""The harness runs a cell from data files alone: a configuration, a traffic
mix and a per-layer metric added as new files in a temporary directory are
found by their names; off a TPU the real entry point refuses."""

import json
import subprocess
import sys

from bench_fixtures import HARNESS, run_cell


def test_new_config_mix_and_metric_are_found(tiny, capsys, monkeypatch):
    import serving

    monkeypatch.setattr(serving, "TRACE_SECONDS", 0.0)  # one traced wave, then the rest
    root, base = tiny
    (base / "metrics" / "waves.count.py").write_text(
        "def read(run):\n    return float(len(run['waves']))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "waves.count", "unit": "waves", "better": "higher",
                               "source": "host_clock", "layer": "scheduler: ServeEngine",
                               "moves": "gen_tokens_per_s", "workloads": ["tiny.waves"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, res = run_cell(tiny, capsys, "tiny.waves", trace=1)
    assert rc == 0 and res["correct"], res
    assert res["metrics"]["waves.count"]["value"] >= 1
    assert {"compile_s", "engine.prefill_ms", "engine.decode_step_ms", "engine.ttft_p95_ms",
            "engine.tpot_p95_ms"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"


def test_end_to_end_metrics_of_a_serving_cell(tiny, capsys):
    rc, res = run_cell(tiny, capsys, "tiny.waves", seed=2**33 + 1)
    assert rc == 0 and res["correct"], res
    assert set(res["metrics"]) == {"setup_s", "gen_tokens_per_s", "ttft_p95_ms"}
    assert res["failed"] == 0 and res["attempted"] % 4 == 0
    assert res["checks"]["logit_gap"]["limit"] == 0.02


def test_refuses_without_a_tpu(tmp_path):
    out = subprocess.run([sys.executable, str(HARNESS / "run.py"), "--workload",
                          "starcoder2-3b.decode", "--seed", "1", "--seconds", "1"],
                         capture_output=True, text=True, timeout=300,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                              "HOME": str(tmp_path)})
    assert out.returncode == 3
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
