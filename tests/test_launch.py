"""Entry points: launchers, mesh construction, and chip_smoke.py at tiny sizes.

chip_smoke.py's own checks (engine logits against a full forward pass,
finite losses, 2x2-mesh losses against one device, parameter spread) run here
on the CPU with `--reduced`; off a TPU it must never print its ok line.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

import jax

from repro.launch import serve, train
from repro.launch.mesh import make_mesh

# JAX compile-heavy: excluded from the fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("launcher", [serve, train])
def test_launchers_run_the_published_config_by_default(launcher):
    args = launcher.build_parser().parse_args(["--arch", "starcoder2-3b"])
    assert args.reduced is False
    assert launcher.build_parser().parse_args(["--arch", "x", "--reduced"]).reduced


def test_serve_main_reports_engine_and_requests():
    before = jax.config.jax_compilation_cache_dir
    engine, done = serve.main(["--arch", "starcoder2-3b", "--reduced", "--slots", "2",
                               "--requests", "2", "--prompt-len", "8", "--max-new", "3",
                               "--max-len", "16"])
    assert jax.config.jax_compilation_cache_dir == before  # only __main__ sets it
    assert all(r.done and len(r.out_tokens) == 3 for r in done)
    assert engine.decode_steps == 2 and engine.prefill_s > 0 and engine.decode_s > 0


def test_train_layers_cuts_depth_keeps_widths():
    state, hist = train.main(["--arch", "starcoder2-3b", "--reduced", "--layers", "1",
                              "--steps", "2", "--batch", "2", "--seq", "32"])
    wq = state["params"]["layers"]["attn"]["wq"]
    assert wq.shape[0] == 1  # one stacked layer
    assert wq.shape[1:] == (128, 128)  # reduced config widths untouched
    assert len(hist) == 2


def test_make_mesh_axes_are_auto():
    from jax.sharding import AxisType

    mesh = make_mesh((1, 1), ("data", "model"))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)
    with pytest.raises(ValueError, match="needs 2 devices"):
        make_mesh((2, len(jax.devices())), ("data", "model"))


def test_chip_smoke_refuses_without_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("the refusal is off-TPU only")
    assert _chip_smoke().main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "no TPU" in err


def test_chip_smoke_reduced_checks_pass_without_ok_line(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("off-TPU the reduced run must not print ok")
    from repro.serve.engine import ServeEngine

    assert _chip_smoke().main(["--reduced"]) != 0
    out, _ = capsys.readouterr()
    assert "serve check:" in out and "\ntrain:" in out
    assert '"ok"' not in out
    assert serve.ServeEngine is ServeEngine  # the smoke's recording engine is undone


def test_chip_smoke_four_chips_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
            "sys.exit(chip_smoke.main(['--reduced', '--four-chips']))")
    res = subprocess.run([sys.executable, "-c", code, ROOT], env=env, capture_output=True,
                         text=True, timeout=600)
    assert res.returncode != 0, res.stdout  # never ok off the chip
    assert "mesh check:" in res.stdout, res.stderr[-3000:]
    assert "param placement:" in res.stdout
    assert '"ok"' not in res.stdout
