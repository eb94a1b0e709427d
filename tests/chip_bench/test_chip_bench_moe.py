"""What a mixture-of-experts configuration asks of the harness, as new files
only: a `moe` section in the configuration file, expert matrices drawn at
their own fan-in, the section's numbers in the reference's static argument,
every counter of the engine in the wave records, and topic-skewed waves. The
existing cells' weights, references and readings do not move."""

import json

import jax
import numpy as np
import pytest
from bench_fixtures import HARNESS, TINY, WAVES, run_cell

import family
import peaks
import reference
import traffic
import weights

# deepseek-moe-16b's program config at a tiny size: one leading dense layer,
# then two layers of 8 routed experts, top-2, and 2 shared experts
MOE_TINY = {
    "source": "test", "arch": "deepseek-moe-16b", "reference": "moe_test",
    "num_layers": 3, "d_model": 128, "num_heads": 4, "num_kv_heads": 4, "head_dim": 32,
    "d_ff": 64, "vocab_size": 256, "norm": "rmsnorm", "act": "silu", "gated_mlp": True,
    "qk_norm": False, "tie_embeddings": False, "sliding_window": None, "rope_theta": 10000.0,
    "dtype": "bfloat16", "param_dtype": "bfloat16",
    "moe": {"num_experts": 8, "top_k": 2, "d_ff": 64, "num_shared_experts": 2,
            "first_k_dense": 1, "dense_d_ff": 256},
    "assumed": {"weights": "random from the seed"},
}

# a family that knows the program's parameter tree of that architecture
MOE_FAMILY = '''
COVERS = {"family": "moe", "norm": "rmsnorm", "gated_mlp": True}
SCOPES = ("embed", "norm", "attn", "mlp", "head")


def layout(cfg):
    d, V, p, f = cfg["d_model"], cfg["vocab_size"], cfg["param_dtype"], "float32"
    q, kv = cfg["num_heads"] * cfg["head_dim"], cfg["num_kv_heads"] * cfg["head_dim"]
    m = cfg["moe"]
    E, ff, shared = m["num_experts"], m["d_ff"], m["d_ff"] * m["num_shared_experts"]

    def block(mlp):
        return {"ln1/scale": ((d,), f), "ln2/scale": ((d,), f),
                "attn/wq": ((d, q), p), "attn/wk": ((d, kv), p),
                "attn/wv": ((d, kv), p), "attn/wo": ((q, d), p), **mlp}

    dense = {f"mlp/{n}": (s, p) for n, s in
             (("wi", (d, m["dense_d_ff"])), ("wg", (d, m["dense_d_ff"])),
              ("wo", (m["dense_d_ff"], d)))}
    moe = {"moe/router": ((d, E), f), "moe/wi": ((E, d, ff), p), "moe/wg": ((E, d, ff), p),
           "moe/wo": ((E, ff, d), p), "moe/shared/wi": ((d, shared), p),
           "moe/shared/wg": ((d, shared), p), "moe/shared/wo": ((shared, d), p)}
    out = {"embed/tok": ((V, d), p), "final_norm/scale": ((d,), f), "lm_head/w": ((d, V), p)}
    for i in range(m["first_k_dense"]):
        out.update({f"head_layers/{i}/{n}": s for n, s in block(dense).items()})
    L = cfg["num_layers"] - m["first_k_dense"]
    out.update({f"layers/{n}": ((L, *s), dt) for n, (s, dt) in block(moe).items()})
    return out
'''


@pytest.fixture
def moe(tiny):
    """The tiny MoE configuration and its family as files beside the tiny cell."""
    root, base = tiny
    (base / "configs" / "moe.json").write_text(json.dumps(MOE_TINY))
    (base / "families" / "moe_test.py").write_text(MOE_FAMILY)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "moe", "source": "test", "file": "bench/configs/moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "moe.waves", "config": "moe", "traffic": "waves",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = json.loads((base / "configs" / "moe.json").read_text())
    return cfg, family.load(base, cfg)


def program(cfg, fam):
    import run
    from repro.models.transformer import Model

    mcfg = run.model_config(cfg, fam)
    return mcfg, Model(mcfg)


def test_a_moe_section_reaches_the_program(moe):
    from repro.configs.base import MoECfg

    cfg, fam = moe
    mcfg, model = program(cfg, fam)
    assert isinstance(mcfg.moe, MoECfg)
    for k, v in cfg["moe"].items():
        assert getattr(mcfg.moe, k) == v, k
    assert mcfg.moe.capacity_factor == 1.25  # a field the file leaves is the arch's own
    weights.check_tree(fam, cfg, model.pshapes())
    assert model.pshapes()["layers"]["moe"]["wi"].shape == (2, 8, 128, 64)


def test_a_null_section_is_no_section(moe):
    cfg, fam = moe
    cfg = {**cfg, "moe": None}
    mcfg, _ = program(cfg, fam)
    assert mcfg.moe is None
    assert family.sections(cfg) == {} and reference.static(cfg) == parent_static(cfg)


def test_expert_matrices_draw_at_their_fan_in(moe):
    cfg, fam = moe
    tree = weights.flatten(weights.make(fam, cfg, 2**33 + 7))
    key = weights.seed_key(2**33 + 7)
    for name in ("wi", "wg", "wo"):
        path = f"layers/moe/{name}"
        shape, dt = fam.layout(cfg)[path]
        a = np.asarray(tree[path], np.float32)
        assert a.shape == shape
        std = a.std(axis=(-2, -1))  # one per layer and expert
        np.testing.assert_allclose(std, shape[-2] ** -0.5, rtol=0.03)
        flat = a.reshape(shape[0] * shape[1], -1)
        assert len({row.tobytes() for row in flat}) == len(flat)  # every expert its own
        one = weights.layer_leaf(key, path, shape[1:], dt, 1)
        np.testing.assert_array_equal(np.asarray(one), a[1])
    # a matrix of rank 2 keeps its fan-in, stacked or not
    router = np.asarray(tree["layers/moe/router"])
    np.testing.assert_allclose(router.std(axis=(-2, -1)), 128**-0.5, rtol=0.03)
    dense_wo = np.asarray(tree["head_layers/0/mlp/wo"], np.float32)
    np.testing.assert_allclose(dense_wo.std(), 256**-0.5, rtol=0.03)


def test_the_dense_family_refuses_a_moe_file(tiny, moe, capsys):
    _, base = tiny
    cfg = {**MOE_TINY, "reference": "dense"}
    (base / "configs" / "moe.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="family 'dense' does not cover .*MoECfg"):
        run_cell(tiny, capsys, "moe.waves")
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("config,section,says", [
    ("moe", {"num_experts": 8, "top_k": 2, "expert_count": 3}, "expert_count"),
    ("tiny", {"num_experts": 8, "top_k": 2, "d_ff": 64}, "'moe'"),
])
def test_a_section_the_program_lacks_is_refused(tiny, moe, capsys, config, section, says):
    _, base = tiny
    path = base / "configs" / f"{config}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "moe": section}))
    with pytest.raises(KeyError, match=says):
        run_cell(tiny, capsys, f"{config}.waves")
    assert capsys.readouterr().out == ""


def parent_static(cfg):
    """`reference.static` as it was before sections existed."""
    return tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))))


def test_static_carries_the_section(moe):
    cfg, _ = moe
    got = dict(reference.static(cfg))
    assert {k: v for k, v in got.items() if k.startswith("moe.")} == {
        f"moe.{k}": v for k, v in MOE_TINY["moe"].items()}
    assert not any(k.startswith("assumed") for k in got)
    assert set(parent_static(cfg)) <= set(reference.static(cfg))
    hash(reference.static(cfg))


@pytest.mark.parametrize("name", ["starcoder2-3b", "minitron-8b-l16"])
def test_static_is_unchanged_without_sections(name):
    cfg = json.loads((HARNESS / "configs" / f"{name}.json").read_text())
    assert family.sections(cfg) == {}
    assert reference.static(cfg) == parent_static(cfg)


FIVE = ("prefill_s", "decode_s", "decode_steps", "sample_s", "dispatch_s")
# a wave's record before every engine counter was kept
PARENT_WAVE = ("t0", "t1", "traced", "tokens", *FIVE)


def test_wave_records_hold_every_engine_counter(tiny, capsys, monkeypatch):
    import run
    import serving
    from repro.serve.engine import ServeEngine

    root, base = tiny
    monkeypatch.setattr(serving, "TRACE_SECONDS", 0.0)  # one traced wave, then the rest
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind, peaks.PEAKS["TPU v5 lite"])
    after = []  # the engine's five counters after each serve(), the warm-up first
    serve = ServeEngine.serve

    def serve_and_count(self, reqs):
        out = serve(self, reqs)
        self.rows_routed = 7 * len(reqs)  # a counter that a later program adds
        after.append({k: getattr(self, k) for k in FIVE})
        return out

    monkeypatch.setattr(ServeEngine, "serve", serve_and_count)
    readings, records = {}, []
    load = run.load_reader

    def read_both(base, name):
        read = load(base, name)

        def both(rec):
            records.append(rec)
            parent = {**rec, "waves": [{k: w[k] for k in PARENT_WAVE} for w in rec["waves"]]}
            readings[name] = (read(rec), read(parent))
            return readings[name][0]
        return both

    monkeypatch.setattr(run, "load_reader", read_both)
    (base / "metrics" / "rows_routed.py").write_text(
        "def read(run):\n"
        "    rows = [w.get('rows_routed') for w in run['waves']]\n"
        "    return None if None in rows else float(sum(rows))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "rows_routed", "unit": "rows", "better": "higher",
                               "source": "program_counter", "layer": "model step",
                               "moves": "gen_tokens_per_s", "workloads": ["tiny.waves"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, res = run_cell(tiny, capsys, "tiny.waves", trace=1)
    assert rc == 0 and res["correct"], res
    waves = records[-1]["waves"]
    assert [{k: w[k] for k in FIVE} for w in waves] == after[1:]
    assert all(w["rows_routed"] == 28 and w["slots"] == 4 for w in waves)
    assert res["metrics"]["rows_routed"]["value"] == 28.0 * len(waves)
    # every existing reader reads the same number as from the parent's record
    existing = {p.stem for p in (HARNESS / "metrics").glob("*.py")}
    assert existing <= set(readings)
    for name in existing:
        assert readings[name][0] == readings[name][1], name
    for name in ("engine.decode_step_ms", "engine.host_step_ms", "engine.prefill_ms",
                 "mfu.serve"):
        assert readings[name][0] is not None, name


def parent_prompts(seed, wave, slots, prompt_len, vocab):
    """`traffic.prompts` as it was before `topic_ids` existed."""
    rng = np.random.default_rng([seed, 0, wave])
    return rng.integers(0, vocab, size=(slots, prompt_len), dtype=np.int32)


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11, 2**33 + 1])
@pytest.mark.parametrize("shape", [(4, 32, 256), (16, 1024, 49152), (8, 3072, 256000)])
def test_prompts_without_topics_are_the_parents(seed, shape):
    for wave in (0, 1, 7):
        got = traffic.prompts(seed, wave, *shape)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, parent_prompts(seed, wave, *shape))


@pytest.mark.parametrize("n", [1, 12, 256])
def test_topic_ids_keep_each_wave_within_one_set(n):
    V = 256 if n == 256 else 102400
    sets = []
    for wave in range(4):
        p = traffic.prompts(2**33 + 1, wave, 8, 512, V, n)
        assert p.dtype == np.int32 and p.shape == (8, 512)
        ids = np.unique(p)
        assert len(ids) == n and 0 <= ids.min() and ids.max() < V
        sets.append(frozenset(ids.tolist()))
        np.testing.assert_array_equal(p, traffic.prompts(2**33 + 1, wave, 8, 512, V, n))
    if 1 < n < V:
        assert len(set(sets)) == 4  # the set differs from wave to wave


@pytest.mark.parametrize("n", [0, -3, 1.5, True, None])
def test_a_mix_with_too_few_topics_is_refused(tmp_path, n):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "m.json").write_text(json.dumps({**WAVES, "topic_ids": n}))
    with pytest.raises(ValueError, match="topic_ids"):
        traffic.load(tmp_path, "m")


def test_more_topics_than_the_vocabulary_are_refused():
    with pytest.raises(ValueError, match="topic_ids 257"):
        traffic.prompts(1, 0, 4, 32, 256, 257)


def test_a_cell_serves_topic_skewed_waves(tiny, capsys, monkeypatch):
    from repro.serve.engine import ServeEngine

    _, base = tiny
    (base / "traffic" / "waves.json").write_text(json.dumps({**WAVES, "topic_ids": 5}))
    served = []
    serve = ServeEngine.serve

    def serve_and_keep(self, reqs):
        served.append(np.stack([r.prompt for r in reqs]))
        return serve(self, reqs)

    monkeypatch.setattr(ServeEngine, "serve", serve_and_keep)
    rc, res = run_cell(tiny, capsys, "tiny.waves", seed=2**32 + 3)
    assert rc == 0 and res["correct"], res
    waves = served[1:]  # after the warm-up
    assert waves
    for i, p in enumerate(waves):
        assert len(np.unique(p)) == 5
        np.testing.assert_array_equal(p, traffic.prompts(2**32 + 3, i, 4, 32, TINY["vocab_size"], 5))
