"""Helpers of the chip benchmark's tests: the harness on the path, a tiny
serving cell in a temporary directory, run on the CPU, and its readings."""

import json
import shutil
import sys
from pathlib import Path

HARNESS = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(HARNESS))

# tiny sizes of the published starcoder2-3b configuration, in its own dtypes
TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
            vocab_size=256)
WAVES = {"kind": "waves", "slots": 4, "prompt_len": 32, "new_tokens": 8, "max_len": 64,
         "check_requests": 4}
# the tiny cell's limit, from CPU readings of these sizes: served-token gap 0.0
# on sound runs against 0.045-0.12 for the float8 control
TINY_LIMITS = {"logit_gap": {"limit": 0.02}}


def write_bench(root: Path) -> Path:
    """BENCHMARK.json and the harness's data files for the cell `tiny.waves`;
    returns the directory of the data files."""
    base = root / "bench"
    for d in ("configs", "traffic", "limits"):
        (base / d).mkdir(parents=True)
    shutil.copytree(HARNESS / "metrics", base / "metrics")
    shutil.copytree(HARNESS / "families", base / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((HARNESS / "configs" / "starcoder2-3b.json").read_text())
    cfg.update(TINY)
    (base / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (base / "traffic" / "waves.json").write_text(json.dumps(WAVES))
    (base / "limits" / "tiny.waves.json").write_text(json.dumps(TINY_LIMITS))
    bench = json.loads((HARNESS.parents[1] / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.waves", "config": "tiny", "traffic": "waves",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.waves"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return base


def run_cell(tiny, capsys, cell, *extra, seed=5, seconds=0.5, trace=0):
    """Run the harness on the CPU; returns (exit code, result or None)."""
    import run

    root, base = tiny
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(trace), *extra], root=root, base=base, require_tpu=False,
                  compile_cache=False)
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return rc, result


def control_readings(tiny, cell, seeds):
    """Readings of the program and of the float8 control on `seeds`, as
    `calibrate.py` takes them on the chip."""
    import calibrate
    import family
    import run
    import traffic

    root, base = tiny
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((base / "configs" / "tiny.json").read_text())
    mix = traffic.load(base, run.entry(bench["workloads"], cell, "workload")["traffic"])
    fam = family.load(base, cfg)
    ctx = {"config": cfg, "mix": mix, "family": fam, "model_config": run.model_config(cfg, fam)}
    return calibrate.serve_readings(ctx, seeds, lambda *a: None)


# the fault tests compare every request the window finishes, so that what they
# compare does not depend on how many waves fit the window: of a sample of 4,
# which 4 depends on that count, and with a stale cache the 4 drawn after 64,
# 100, 112, 292, 312, 400, 524 or 624 requests all serve the reference's tokens
COMPARE_ALL = {**WAVES, "check_requests": 10**6}


def check_fault(tiny, capsys, monkeypatch, cell, fault, fails):
    _, base = tiny
    (base / "traffic" / "waves.json").write_text(json.dumps(COMPARE_ALL))
    fault(monkeypatch)
    rc, res = run_cell(tiny, capsys, cell, seconds=0.3)
    assert rc == 0 and res is not None
    assert res["correct"] is False
    c = res["checks"][fails]
    assert c["value"] > c["limit"], res["checks"]

