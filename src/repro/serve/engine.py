"""Batched serving engine: prefill + autoregressive decode over slot batches.

Implements the paper's two inference phases as separate compiled programs:
  * prefill (summarization) — fat-GEMM, usually compute-bound (§6.1, Table 4),
  * decode (generation)     — skinny GEMM/GEMV over the KV cache, memory-bound.

Slot-based continuous batching (lite): a fixed decode batch of `slots`; each
finished request frees its slot, queued prompts are prefilled into free slots
and their caches spliced in. Cache buffers are donated across decode steps,
yet the update is not in place: the one-position write (`attn/kv_write`) is
cheap, but the layer scan slices each layer's K and V out of the stacked
cache and writes them back whole, and the program copies the stacked cache
on entry. In a profiler trace on TPU v5e (starcoder2-3b, 16 slots, 4096-token
cache) those copies take about 30 ms of a 44-ms step, outside every named
scope. Limitation (recorded): the cache position is a single scalar, so
admitted prompts are aligned to the current position — adequate for the
near-equal-length request mixes the benchmarks use.

The programs lower as `jit_prefill` and `jit_decode`. `serve()` writes host
spans into the profiler's trace (inactive when no trace runs): `engine.admit`
holding `engine.prefill` and `engine.splice`, `engine.sample` once a round,
and `engine.decode` holding `engine.dispatch` and `engine.fetch`.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.transformer import Model


@dataclass
class Request:
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    temperature: float = 0.0
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, model: Model, params, *, max_len: int, slots: int = 8, seed: int = 0):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.slots = slots
        self.key = jax.random.PRNGKey(seed)
        # of the last serve() call: decode iterations, and host seconds spent
        # in prefill (with the cache splice) and in decode, each up to the
        # logits' arrival on the host; of those, the host's own work: the
        # argmax and bookkeeping loop, and the decode step's dispatch
        self.decode_steps = 0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.sample_s = 0.0
        self.dispatch_s = 0.0

        def prefill(p, b):
            return model.prefill(p, b, max_len=max_len)

        def decode(p, c, t):
            return model.decode_step(p, c, t)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(1,))

    # ----------------------------------------------------------- single batch
    def generate(self, prompts: list[np.ndarray], max_new_tokens: int,
                 temperature: float = 0.0) -> list[list[int]]:
        """Generate for a batch of equal-priority prompts (padded to one batch)."""
        B = len(prompts)
        S = max(len(p) for p in prompts)
        # left-pad to common length with token 0; positions beyond prompt are
        # attended (simplification: callers pass equal-length prompts in the
        # benchmarks; ragged batching is handled by the slot scheduler below)
        toks = np.zeros((B, S), np.int32)
        for i, p in enumerate(prompts):
            toks[i, S - len(p) :] = p
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(toks)})
        outs: list[list[int]] = [[] for _ in range(B)]
        for _ in range(max_new_tokens):
            nxt = self._sample(logits, temperature)  # (B,)
            for i in range(B):
                outs[i].append(int(nxt[i]))
            logits, cache = self._decode(self.params, cache, nxt[:, None])
        return outs

    def _sample(self, logits: jax.Array, temperature: float) -> jax.Array:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(sub, logits / temperature, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------ slot-based server
    def serve(self, requests: list[Request], *, eos: int | None = None) -> list[Request]:
        """Continuous-batching-lite scheduler over a fixed slot count.

        `decode_steps`, `prefill_s`, `decode_s`, `sample_s` and `dispatch_s`
        describe the last call."""
        pending = list(requests)
        active: list[Request | None] = [None] * self.slots
        cache = None
        logits_np = None
        self.decode_steps = 0
        self.prefill_s = self.decode_s = self.sample_s = self.dispatch_s = 0.0
        while pending or any(a is not None for a in active):
            # fill free slots: batch-prefill all newly admitted requests
            admit = []
            for s in range(self.slots):
                if active[s] is None and pending:
                    active[s] = pending.pop(0)
                    admit.append(s)
            if admit:
                t0 = time.perf_counter()
                with TraceAnnotation("engine.admit"):
                    cache, logits_np = self._admit(admit, active, cache, logits_np)
                self.prefill_s += time.perf_counter() - t0
            live = [s for s in range(self.slots) if active[s] is not None]
            if not live:
                break
            t0 = time.perf_counter()
            with TraceAnnotation("engine.sample"):
                nxt = np.zeros((self.slots,), np.int32)
                for s in live:
                    r = active[s]
                    tok = int(np.argmax(logits_np[s]))
                    r.out_tokens.append(tok)
                    nxt[s] = tok
                    if (eos is not None and tok == eos) or len(r.out_tokens) >= r.max_new_tokens:
                        r.done = True
                        active[s] = None
            self.sample_s += time.perf_counter() - t0
            if not any(active[s] is not None for s in live):
                # every live slot finished this step: the decode would only
                # produce logits for freed slots (stale by the next admit)
                continue
            t0 = time.perf_counter()
            with TraceAnnotation("engine.decode"):
                with TraceAnnotation("engine.dispatch"):
                    logits, cache = self._decode(self.params, cache, jnp.asarray(nxt)[:, None])
                t1 = time.perf_counter()
                with TraceAnnotation("engine.fetch"):
                    logits_np = np.array(logits)
            self.decode_s += time.perf_counter() - t0
            self.dispatch_s += t1 - t0
            self.decode_steps += 1
        return requests

    def _admit(self, slots_to_fill, active, cache, logits_np):
        """Prefill admitted prompts as one padded batch; splice into slot cache."""
        B = self.slots
        S = max(len(active[s].prompt) for s in slots_to_fill)
        toks = np.zeros((B, S), np.int32)
        for s in slots_to_fill:
            toks[s, S - len(active[s].prompt) :] = active[s].prompt
        with TraceAnnotation("engine.prefill"):
            logits, new_cache = self._prefill(self.params, {"tokens": jnp.asarray(toks)})
            new_logits = np.array(logits)
        if cache is None:
            return new_cache, new_logits
        # splice: batch dim is leading on every cache leaf except "pos"
        mask = np.zeros((B,), bool)
        for s in slots_to_fill:
            mask[s] = True
        m = jnp.asarray(mask)

        def splice(old, new):
            if old.ndim == 0:  # pos: keep max (slots decode in lockstep)
                return jnp.maximum(old, new)
            if old.shape[0] == B:
                sel = m.reshape((B,) + (1,) * (old.ndim - 1))
                return jnp.where(sel, new, old)
            # stacked-layer leaves: (L, B, ...)
            sel = m.reshape((1, B) + (1,) * (old.ndim - 2))
            return jnp.where(sel, new, old)

        with TraceAnnotation("engine.splice"):
            cache = jax.tree.map(splice, cache, new_cache)
            if logits_np is not None:
                logits_np[mask] = new_logits[mask]
        return cache, logits_np
