"""A configuration brings its family as a file of its own: the harness finds it
by the name the configuration gives, refuses a configuration that names none
or lies outside what its family covers, and the dense family gives, bit for
bit, the weights, reference logits and work counts the harness gave before
families existed."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_fixtures import HARNESS, TINY, run_cell

import family
import flops
import peaks
import weights


def set_config(base, **changes):
    path = base / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    cfg.update(changes)
    for k in [k for k, v in changes.items() if v is None]:
        del cfg[k]
    path.write_text(json.dumps(cfg))


def test_a_family_in_a_new_file_is_found_by_its_name(tiny, capsys, monkeypatch):
    import serving

    monkeypatch.setattr(serving, "TRACE_SECONDS", 0.0)  # one traced wave, then the rest
    # the CPU takes a v5e's peaks, so that the readers of work counts read
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind, peaks.PEAKS["TPU v5 lite"])
    root, base = tiny
    (base / "families" / "dense_copy.py").write_text(
        (base / "families" / "dense.py").read_text())
    set_config(base, reference="dense_copy")
    rc, res = run_cell(tiny, capsys, "tiny.waves", trace=1)
    assert rc == 0 and res["correct"], res
    # the work counts of `mfu.serve` and `roofline.serve` came from the new file
    assert {"mfu.serve", "roofline.serve", "engine.host_step_ms"} <= set(res["metrics"])
    assert family.of({"reference": "dense_copy"}).__file__ == str(
        (base / "families" / "dense_copy.py").resolve())
    assert not (HARNESS / "families" / "dense_copy.py").exists()


@pytest.mark.parametrize("name,says", [(None, "names no family"), ("moe_not_here", "moe_not_here")])
def test_a_configuration_without_its_family_is_refused(tiny, capsys, name, says):
    _, base = tiny
    set_config(base, reference=name)
    with pytest.raises((ValueError, FileNotFoundError), match=says):
        run_cell(tiny, capsys, "tiny.waves")
    assert capsys.readouterr().out == ""


def test_a_configuration_outside_its_family_is_refused(tiny, capsys):
    _, base = tiny
    set_config(base, norm="rmsnorm")
    with pytest.raises(ValueError, match="family 'dense' does not cover .*rmsnorm"):
        run_cell(tiny, capsys, "tiny.waves")


def test_a_failed_scope_reduction_loses_only_its_readers(tiny, capsys, monkeypatch):
    import scopes
    import serving

    def broken(*a, **k):
        raise RuntimeError("planted")

    monkeypatch.setattr(serving, "TRACE_SECONDS", 0.0)
    monkeypatch.setattr(scopes, "reduce_xplane", broken)
    rc, res = run_cell(tiny, capsys, "tiny.waves", trace=1)
    assert rc == 0 and res["correct"], res
    assert "engine.host_step_ms" in res["metrics"] and "idle.fetch_ms" not in res["metrics"]


def digest(items) -> str:
    """The first 16 hex digits of the sha256 of names and arrays' bytes."""
    h = hashlib.sha256()
    for name, a in items:
        h.update(name.encode())
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


# What the harness gave before families existed, when `weights.py`,
# `reference.py` and `flops.py` held the dense decoder alone, on the CPU:
# weights of seed 11 at `TINY` sizes in the published dtypes, leaves in order
# of path; logits of the reference and its float8 control for 3 rows of 40
# tokens from position 30; and a wave's operations and roofline seconds at the
# cell's shapes on a TPU v5e.
PARENT = {
    "starcoder2-3b": {"weights": "25344af28e0b7638", "ref": "a08ec2fdc01a2533",
                      "ctl": "3cd8933cbf5efd4c", "shape": (16, 1024, 127),
                      "wave_flops": 110543926984704.0, "wave_roofline_s": 1.5171807486021915},
    "minitron-8b-l16": {"weights": "571280654cdafc1a", "ref": "8968a148edd15f5b",
                        "ctl": "66bf99d7bc2a225f", "shape": (8, 3072, 15),
                        "wave_flops": 167735493394432.0, "wave_roofline_s": 1.0267560110016596},
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_dense_family_reproduces_the_parent(name):
    full = json.loads((HARNESS / "configs" / f"{name}.json").read_text())
    assert full["reference"] == "dense"
    fam = family.load(HARNESS, full)
    want = PARENT[name]
    assert flops.wave_flops(full, *want["shape"]) == want["wave_flops"]
    assert flops.wave_roofline_s(full, *want["shape"], peaks.peaks("TPU v5 lite")) \
        == want["wave_roofline_s"]

    cfg = {**full, **TINY}
    tree = weights.flatten(weights.make(fam, cfg, 11))
    assert digest((k, tree[k].astype(jnp.float32)) for k in sorted(tree)) == want["weights"]
    toks = np.random.default_rng(3).integers(0, TINY["vocab_size"], (3, 40)).astype(np.int32)
    assert digest([("", fam.scored_logits(cfg, 11, toks, 30))]) == want["ref"]
    assert digest([("", fam.scored_logits(cfg, 11, toks, 30, mode="fp8"))]) == want["ctl"]
