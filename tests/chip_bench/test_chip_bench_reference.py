"""The plain reference against the program, at a tiny size in float32 on the
CPU, and the seeded weights it shares with the harness."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_fixtures import TINY, write_bench

import weights


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    import json

    import family
    import run

    root = tmp_path_factory.mktemp("bench")
    base = write_bench(root)
    cfg = json.loads((base / "configs" / "tiny.json").read_text())
    cfg.update(dtype="float32", param_dtype="float32")
    fam = family.load(base, cfg)
    sys_path_program = str(run.ROOT / "src")
    import sys

    if sys_path_program not in sys.path:
        sys.path.insert(0, sys_path_program)
    from repro.models.transformer import Model

    mcfg = dataclasses.replace(run.model_config(cfg, fam), attn_impl="dense")
    return cfg, fam, Model(mcfg)


def test_seed_keeps_all_its_bits():
    a = jax.random.key_data(weights.seed_key(5))
    b = jax.random.key_data(weights.seed_key(2**32 + 5))
    assert not np.array_equal(a, b)
    with pytest.raises(ValueError):
        weights.seed_key(-1)


def test_layer_by_layer_draw_matches_the_whole_tree(setup):
    cfg, fam, _ = setup
    tree = weights.flatten(weights.make(fam, cfg, 9))
    key = weights.seed_key(9)
    for path, (shape, dt) in fam.layout(cfg).items():
        if path.startswith("layers/"):
            one = weights.layer_leaf(key, path, shape[1:], dt, 1)
            np.testing.assert_array_equal(np.asarray(tree[path][1]), np.asarray(one))


def test_program_tree_is_the_layout(setup):
    cfg, fam, model = setup
    weights.check_tree(fam, cfg, model.pshapes())
    with pytest.raises(RuntimeError):
        weights.check_tree(fam, {**cfg, "d_ff": 2 * cfg["d_ff"]}, model.pshapes())


@pytest.mark.parametrize("tied", [True, False])
def test_logits_match_the_program(setup, tied):
    cfg, fam, _ = setup
    cfg = {**cfg, "tie_embeddings": tied}
    import run
    from repro.models.transformer import Model

    model = Model(dataclasses.replace(run.model_config(cfg, fam), attn_impl="dense"))
    weights.check_tree(fam, cfg, model.pshapes())
    params = weights.make(fam, cfg, 3)
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 24)).astype(np.int32)
    hidden, _ = model.forward(params, {"tokens": jnp.asarray(toks)})
    with jax.default_matmul_precision("highest"):
        prog = model._head(params, hidden[:, 10:])
    ref = fam.scored_logits(cfg, 3, toks, 10)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref), atol=2e-4, rtol=2e-4)
    ctl = fam.scored_logits(cfg, 3, toks, 10, mode="fp8")
    assert float(jnp.abs(ctl - ref).max()) > 1e-2
