"""Multi-device tests (subprocess: device count must be set before jax init).

Covers: sharded training == single-device numerics, multi-pod mesh train step,
elastic checkpoint reshard (1 device save -> 8 device restore), sharded decode
== single-device decode with the cache keeping its sharding across steps."""

import os
import subprocess
import sys
import textwrap

import pytest

# JAX compile-heavy: excluded from the fast tier (pytest -m "not slow")
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str, devices: int = 8, timeout: int = 420):
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {SRC!r})
        """
    ) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=timeout)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    return res.stdout


def test_sharded_train_matches_single_device():
    out = _run(
        """
        import jax, numpy as np
        from repro.configs import get_config
        from repro.configs.base import ParallelConfig, TrainConfig
        from repro.data.pipeline import SyntheticLM
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import Model
        from repro.parallel.axes import make_rules
        from repro.train.trainer import Trainer

        cfg = get_config("qwen3-14b").reduced()
        model = Model(cfg)
        data = SyntheticLM(cfg.vocab_size, 64, 8)
        tcfg = TrainConfig(steps=3, log_every=100)

        # single-device reference
        tr0 = Trainer(model, ParallelConfig(), tcfg)
        s0 = tr0.init_state()
        s0, h0 = tr0.fit(s0, data, steps=3)

        # (data=2, model=4) sharded
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(dp=("data",), tp=("model",))
        tr1 = Trainer(model, ParallelConfig(), tcfg, mesh=mesh, rules=rules)
        s1 = tr1.init_state()
        s1, h1 = tr1.fit(s1, data, steps=3)
        for a, b in zip(h0, h1):
            assert abs(a["loss"] - b["loss"]) < 2e-3, (a["loss"], b["loss"])
        print("SHARDED_MATCH", h0[-1]["loss"], h1[-1]["loss"])
        """
    )
    assert "SHARDED_MATCH" in out


def test_multipod_mesh_train_step():
    out = _run(
        """
        import jax
        from repro.configs import get_config
        from repro.configs.base import ParallelConfig, TrainConfig
        from repro.data.pipeline import SyntheticLM
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import Model
        from repro.parallel.axes import make_rules
        from repro.train.trainer import Trainer

        cfg = get_config("deepseek-moe-16b").reduced()
        model = Model(cfg)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = make_rules(dp=("pod", "data"), tp=("model",))
        tr = Trainer(model, ParallelConfig(microbatches=2), TrainConfig(steps=2, log_every=100),
                     mesh=mesh, rules=rules)
        state = tr.init_state()
        data = SyntheticLM(cfg.vocab_size, 32, 8)
        state, hist = tr.fit(state, data, steps=2)
        assert all(h["loss"] > 0 for h in hist)
        print("MULTIPOD_OK", hist[-1]["loss"])
        """
    )
    assert "MULTIPOD_OK" in out


def test_elastic_checkpoint_reshard(tmp_path):
    # save on 1 device
    _run(
        f"""
        import jax, jax.numpy as jnp
        from repro.checkpoint.checkpoint import CheckpointManager
        m = CheckpointManager({str(tmp_path)!r})
        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        m.save(1, tree, async_=False)
        print("SAVED")
        """,
        devices=1,
    )
    # restore sharded on 8 devices
    out = _run(
        f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        m = CheckpointManager({str(tmp_path)!r})
        target = {{"w": jnp.zeros((8, 8), jnp.float32)}}
        shardings = {{"w": NamedSharding(mesh, P("data", "model"))}}
        tree, step = m.restore(target, shardings=shardings)
        assert step == 1
        assert len(tree["w"].sharding.device_set) == 8
        np.testing.assert_array_equal(np.asarray(tree["w"]).ravel(), np.arange(64))
        print("RESHARD_OK")
        """
    )
    assert "RESHARD_OK" in out


def test_grad_compression_under_mesh():
    out = _run(
        """
        import jax
        from repro.configs import get_config
        from repro.configs.base import ParallelConfig, TrainConfig
        from repro.data.pipeline import SyntheticLM
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import Model
        from repro.parallel.axes import make_rules
        from repro.train.trainer import Trainer

        cfg = get_config("starcoder2-3b").reduced()
        model = Model(cfg)
        mesh = make_mesh((4, 2), ("data", "model"))
        rules = make_rules(dp=("data",), tp=("model",))
        tr = Trainer(model, ParallelConfig(grad_compress=True),
                     TrainConfig(steps=4, log_every=100), mesh=mesh, rules=rules)
        state = tr.init_state()
        data = SyntheticLM(cfg.vocab_size, 32, 8)
        state, hist = tr.fit(state, data, steps=4)
        assert hist[-1]["loss"] < hist[0]["loss"] + 0.1
        print("COMPRESS_OK", hist[0]["loss"], hist[-1]["loss"])
        """
    )
    assert "COMPRESS_OK" in out


# (mesh shape, context parallel, KV heads): heads over tp; a sequence-sharded
# cache where 2 KV heads do not divide tp=4; sequence over cp and tp
DECODE_MESHES = [((2, 2), False, 2), ((1, 4), False, 2), ((2, 2), True, 1)]


@pytest.mark.parametrize("mesh_shape,cp,kv", DECODE_MESHES)
def test_sharded_decode_matches_single_device(mesh_shape, cp, kv):
    """Decode steps under a mesh give the single-device logits, and the cache
    each step returns keeps the sharding `cache_pspecs` gives it, so the next
    step takes it as its input unchanged."""
    out = _run(
        f"""
        import dataclasses
        import jax, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import Model
        from repro.parallel.axes import make_rules, sanitize_spec_tree, use_mesh

        cp = {cp}
        cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(), num_kv_heads={kv})
        m = Model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        B, S, n, max_len = 4, 16, 4, 32
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + n), 0, cfg.vocab_size)

        def decode(prefill, step):
            logits, cache = prefill(params, {{"tokens": toks[:, :S]}})
            out = []
            for t in range(S, S + n):
                logits, cache = step(params, cache, toks[:, t:t + 1])
                out.append(np.asarray(logits))
            return out

        ref = decode(jax.jit(lambda p, b: m.prefill(p, b, max_len=max_len)),
                     jax.jit(m.decode_step))
        mesh = make_mesh({mesh_shape}, ("data", "model"))
        rules = make_rules(dp=() if cp else ("data",), tp=("model",),
                           context_parallel=("data",) if cp else ())
        with use_mesh(mesh, rules):
            specs = sanitize_spec_tree(m.cache_pspecs(cp=cp), m.cache_shapes(B, max_len, cp=cp), mesh)
            shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
            prefill = jax.jit(lambda p, b: jax.device_put(
                m.prefill(p, b, max_len=max_len, cp=cp), (None, shardings)))
            step = jax.jit(lambda p, c, t: m.decode_step(p, c, t, cp=cp),
                           in_shardings=(None, shardings, None), donate_argnums=(1,))
            got = decode(prefill, step)
        err = max(float(np.abs(a - b).max()) for a, b in zip(ref, got))
        assert err < 1e-4, err
        print("SHARDED_DECODE_MATCH", err)
        """,
        devices=4,
    )
    assert "SHARDED_DECODE_MATCH" in out
