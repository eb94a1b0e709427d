"""Training driver: jit + shardings, checkpoint/restart, straggler watchdog.

Fault-tolerance model (DESIGN.md §2):
  * checkpoint/restart — CheckpointManager (async, atomic); `resume()` restores
    the latest step under the *current* mesh (elastic: a restarted job with a
    different device count re-shards via NamedSharding device_put).
  * straggler mitigation — per-step wall-time EMA; steps slower than
    `straggler_factor` x EMA are logged and counted (on a real pod this signal
    feeds the job controller to hot-swap the slow host; here it is surfaced as
    a metric and tested by injecting an artificial delay).
  * data determinism — batches are keyed by (seed, step), so a restart resumes
    mid-epoch without data loss/duplication.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.base import ParallelConfig, TrainConfig
from repro.models.transformer import Model
from repro.parallel.axes import logical_spec, sanitize_spec_tree, use_mesh
from repro.train.optimizer import adamw_init, opt_state_specs
from repro.train.train_step import make_train_step


@dataclass
class StragglerWatchdog:
    factor: float = 2.5
    ema: float | None = None
    alpha: float = 0.2
    slow_steps: int = 0

    def observe(self, dt: float) -> bool:
        slow = self.ema is not None and dt > self.factor * self.ema
        self.slow_steps += slow
        self.ema = dt if self.ema is None else (1 - self.alpha) * self.ema + self.alpha * dt
        return slow


class Trainer:
    def __init__(self, model: Model, pcfg: ParallelConfig, tcfg: TrainConfig,
                 mesh=None, rules=None):
        self.model = model
        self.pcfg, self.tcfg = pcfg, tcfg
        self.mesh, self.rules = mesh, rules
        self.watchdog = StragglerWatchdog()
        self.ckpt = (
            CheckpointManager(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints)
            if tcfg.checkpoint_dir
            else None
        )
        self._build()

    def _ctx(self):
        from repro.parallel.axes import ShardingRules

        return use_mesh(self.mesh, self.rules or ShardingRules())

    def _build(self):
        with self._ctx():
            step = make_train_step(self.model, self.pcfg, self.tcfg)
            if self.mesh is None:
                self._step = jax.jit(step, donate_argnums=(0,))
                self._state_shardings = None
                return
            pspecs = self.model.pspecs()
            pshapes = self.model.pshapes()
            ospecs = opt_state_specs(pspecs, pshapes, self.tcfg)
            oshapes = jax.eval_shape(lambda p: adamw_init(p, self.tcfg), pshapes)

            def ns(spec_tree, shape_tree):
                st = sanitize_spec_tree(spec_tree, shape_tree, self.mesh)
                return jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), st,
                    is_leaf=lambda s: isinstance(s, P),
                )

            self._state_shardings = {
                "params": ns(pspecs, pshapes),
                "opt": ns(ospecs, oshapes),
            }
            if self.pcfg.grad_compress:
                # error-feedback buffers shard like params (fp32)
                self._state_shardings["grad_error"] = ns(pspecs, pshapes)
            self._batch_sharding = NamedSharding(self.mesh, logical_spec("dp", None))
            # out_shardings pinned to the input layouts: the optimizer update
            # runs on ZeRO-sharded grads/moments and XLA would otherwise leave
            # the new params reduce-scattered (step 2 would then reject them);
            # pinning inserts the ZeRO-1 param all-gather explicitly.
            self._step = jax.jit(
                step,
                in_shardings=(self._state_shardings, None),
                out_shardings=(self._state_shardings, None),
                donate_argnums=(0,),
            )

    def init_state(self, seed: int | None = None) -> dict:
        with self._ctx():
            key = jax.random.PRNGKey(self.tcfg.seed if seed is None else seed)
            sh = self._state_shardings

            def placed(part):  # on a mesh, made sharded: never whole on one device
                return {} if sh is None else {"out_shardings": sh[part]}

            params = jax.jit(self.model.init, **placed("params"))(key)
            opt = jax.jit(lambda p: adamw_init(p, self.tcfg), **placed("opt"))(params)
            state = {"params": params, "opt": opt}
            if self.pcfg.grad_compress:
                err = jax.tree.map(
                    lambda p: jax.numpy.zeros(p.shape, jax.numpy.float32), params
                )
                if self._state_shardings is not None:
                    err = jax.device_put(err, self._state_shardings["grad_error"])
                state["grad_error"] = err
            return state

    def resume(self) -> tuple[dict, int]:
        """Restore latest checkpoint under the current mesh (elastic restart)."""
        assert self.ckpt is not None
        target = jax.eval_shape(lambda: self.init_state())
        shardings = self._state_shardings
        state, step = self.ckpt.restore(target, shardings=shardings)
        return state, step

    def fit(self, state: dict, data_iter, *, steps: int, start_step: int = 0,
            log=print) -> tuple[dict, list[dict]]:
        history = []
        with self._ctx():
            for step_i in range(start_step, start_step + steps):
                batch = next(data_iter) if hasattr(data_iter, "__next__") else data_iter.batch(step_i)
                batch = jax.tree.map(jax.numpy.asarray, batch)
                if self.mesh is not None:
                    batch = jax.device_put(batch, self._batch_sharding)
                t0 = time.perf_counter()
                state, metrics = self._step(state, batch)
                metrics = jax.tree.map(float, jax.device_get(metrics))
                dt = time.perf_counter() - t0
                slow = self.watchdog.observe(dt)
                metrics.update(step=step_i, step_time_s=dt,
                               straggler_flag=bool(slow),
                               slow_steps=self.watchdog.slow_steps)
                history.append(metrics)
                if step_i % max(self.tcfg.log_every, 1) == 0:
                    log(
                        f"step {step_i}: loss={metrics['loss']:.4f} "
                        f"gnorm={metrics['grad_norm']:.3f} dt={dt * 1e3:.0f}ms"
                        + (" [STRAGGLER]" if slow else "")
                    )
                if (
                    self.ckpt is not None
                    and self.tcfg.checkpoint_every
                    and (step_i + 1) % self.tcfg.checkpoint_every == 0
                ):
                    self.ckpt.save(step_i + 1, state)
        if self.ckpt is not None:
            self.ckpt.wait()
        return state, history
