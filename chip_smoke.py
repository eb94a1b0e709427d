"""Run the serving and training paths once on a TPU, at published widths.

  python chip_smoke.py                # one chip: serve, then train
  python chip_smoke.py --four-chips   # 2x2-mesh training against one chip
  python chip_smoke.py --reduced      # tiny sizes on any backend: a rehearsal

Phases, through the entry points a user calls (`repro.launch.serve.main`,
`repro.launch.train.main`), all in this one process:

  serve   starcoder2-3b, all 30 layers, 8 slots, 8 prompts of 1024 tokens,
          64 new tokens each, max_len 2048 (one admission wave). The logits
          each request's last token came from must match a full forward pass
          (`Model.forward` plus the head) over prompt + generated tokens.
  train   starcoder2-3b cut to 4 layers, every width as published: AdamW,
          selective remat, batch 2 x 4096, 5 steps, every loss finite.
  --four-chips replaces both: the 4-layer train config for 3 steps on one
          chip and on a (data=2, model=2) mesh; per-step losses must agree
          and the parameters must be spread over all four devices.

Each phase prints its facts (compile and run seconds, peak device memory).
The last line is `{"ok": true, "device": {...}}`, printed only on a TPU and
only at full size; any failure raises, and exits non-zero.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Relative L2 distance allowed between the engine's logits (prefill + cached
# decode) and a full forward pass. The two paths compute the same function in
# the config's bfloat16 with different operation orders (chunked vs. cached
# attention, different fusions), so they differ by bf16 rounding accumulated
# over the layers: 1.1e-2 at 8 layers and d_model 512 on the CPU, 1.5e-2 to
# 1.7e-2 at 30 layers on a TPU v5e. A wrong answer lands far above it: the
# same logits are 0.87-0.91 away from another request's reference. We expect,
# untested, that one step down in precision (fp8 e4m3, unit roundoff 6.25e-2
# per operation) would exceed it too.
LOGITS_RTOL = 5e-2
# Per-step loss difference allowed between the 2x2 mesh and one chip. The
# sharded program reduces partial sums in another order in bf16: 1.5e-4 to
# 3.8e-4 over 3 steps on a TPU v5e, 1e-4 at d_model 512 on 4 CPU devices.
# At full width the first Adam steps swing the loss by several nats
# (11.3 -> 8.5 -> 16.0 on one chip), which could amplify such differences,
# hence the margin. We expect, untested, that a wrong sharding (a missing
# reduction, half the batch per gradient) moves the loss by tenths or more.
LOSS_ATOL = 2e-2

SERVE = ["--arch", "starcoder2-3b", "--slots", "8", "--requests", "8",
         "--prompt-len", "1024", "--max-new", "64", "--max-len", "2048"]
SERVE_REDUCED = ["--arch", "starcoder2-3b", "--reduced", "--slots", "4", "--requests", "4",
                 "--prompt-len", "32", "--max-new", "8", "--max-len", "64"]
TRAIN = ["--arch", "starcoder2-3b", "--layers", "4", "--batch", "2", "--seq", "4096",
         "--remat", "selective", "--optimizer", "adamw"]
TRAIN_REDUCED = ["--arch", "starcoder2-3b", "--reduced", "--batch", "2", "--seq", "64",
                 "--remat", "selective", "--optimizer", "adamw"]


class CompileClock:
    """Seconds of XLA backend compilation, as JAX reports them."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.seconds = 0.0

        def listen(event, secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.seconds += secs

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def fact(phase: str, **kv) -> None:
    print(f"{phase}: {json.dumps(kv)}", flush=True)


def peak_bytes() -> int | None:
    """Process-lifetime peak of device 0's memory in use, where reported."""
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def recording_engine():
    """ServeEngine that keeps its last decode step's logits. When every
    request finishes on the same step, as in one admission wave of equal
    lengths, slot i's row holds the logits request i's last token came from."""
    from repro.serve.engine import ServeEngine

    class RecordingEngine(ServeEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            decode = self._decode

            def record(params, cache, tokens):
                self.last_decode_logits, cache = decode(params, cache, tokens)
                return self.last_decode_logits, cache

            self._decode = record

    return RecordingEngine


def reference_logits(engine, done):
    """Full forward pass over prompt + out_tokens[:-1], logits at the last one."""
    import jax
    import numpy as np

    model, cfg = engine.model, engine.model.cfg
    seqs = np.stack([np.concatenate([r.prompt, r.out_tokens[:-1]]) for r in done])
    n = seqs.shape[1]
    # the chunked attention takes whole chunks; right padding is invisible to
    # causal attention at position n - 1
    s = -(-n // cfg.attn_chunk) * cfg.attn_chunk
    toks = np.zeros((len(done), s), np.int32)
    toks[:, :n] = seqs
    fwd = jax.jit(lambda p, t: model._head(p, model.forward(p, {"tokens": t})[0][:, n - 1]))
    return np.asarray(fwd(engine.params, toks))


def serve_phase(reduced: bool, clock: CompileClock) -> None:
    import numpy as np

    from repro.launch import serve

    c0, t0 = clock.seconds, time.perf_counter()
    plain, serve.ServeEngine = serve.ServeEngine, recording_engine()
    try:
        engine, done = serve.main(SERVE_REDUCED if reduced else SERVE)
    finally:
        serve.ServeEngine = plain
    wall = time.perf_counter() - t0
    got = np.asarray(engine.last_decode_logits)[: len(done)]
    # greedy: each last token is the argmax of the row it was chosen from
    if [r.out_tokens[-1] for r in done] != got.argmax(-1).tolist():
        raise AssertionError("recorded logits are not those the last tokens came from")
    compile_s = clock.seconds - c0
    decode_tokens = sum(len(r.out_tokens) - 1 for r in done)
    fact("serve", compile_s=compile_s, phase_wall_s=wall, prefill_s=engine.prefill_s,
         decode_s=engine.decode_s, decode_steps=engine.decode_steps,
         decode_tok_per_s=decode_tokens / engine.decode_s,
         peak_bytes_in_use=peak_bytes())

    c0 = clock.seconds
    ref = reference_logits(engine, done)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise AssertionError("non-finite logits")
    norm = np.linalg.norm(ref, axis=-1)
    rel = np.linalg.norm(got - ref, axis=-1) / norm
    # the same distance to another request's reference, for scale
    other = np.linalg.norm(got - np.roll(ref, 1, axis=0), axis=-1) / norm
    fact("serve check", rel_l2_per_slot=rel.tolist(), max_abs=float(np.abs(got - ref).max()),
         rel_l2_to_other_request=other.tolist(), rtol=LOGITS_RTOL,
         reference_compile_s=clock.seconds - c0, peak_bytes_in_use=peak_bytes())
    if not (rel.max() <= LOGITS_RTOL):
        raise AssertionError(f"engine logits differ from the forward pass: {rel.max()}")


def train_phase(argv: list[str], phase: str, clock: CompileClock):
    import numpy as np

    from repro.launch import train

    c0 = clock.seconds
    state, hist = train.main(argv)
    losses = [h["loss"] for h in hist]
    steps = [h["step_time_s"] for h in hist]
    fact(phase, compile_s=clock.seconds - c0, first_step_s=steps[0],
         median_later_step_s=float(np.median(steps[1:])), losses=losses,
         peak_bytes_in_use=peak_bytes())
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss: {losses}")
    return state, losses


def check_spread(params) -> None:
    """Every parameter on all four devices, and no device holding it whole
    where the sharding rules split it."""
    import jax

    leaves = jax.tree.leaves(params)
    per_device: dict[int, int] = {}
    split = total = 0
    for leaf in leaves:
        if len(leaf.sharding.device_set) != 4:
            raise AssertionError(f"leaf {leaf.shape} on {leaf.sharding.device_set}")
        total += leaf.nbytes
        if leaf.addressable_shards[0].data.shape != leaf.shape:
            split += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_device[sh.device.id] = per_device.get(sh.device.id, 0) + sh.data.nbytes
    fact("param placement", bytes_per_device=per_device, total_bytes=total,
         split_share=split / total)
    if split < 0.9 * total or max(per_device.values()) > 0.6 * total:
        raise AssertionError("parameters are not spread over the mesh")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 2x2-mesh training check against one chip")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny sizes on any backend; never prints the ok line")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not (on_tpu or args.reduced):
        print(f"no TPU: JAX found {dev.platform!r}", file=sys.stderr)
        return 1

    from repro.launch.runtime import device_info

    fact("device", **device_info(), compile_cache=jax.config.jax_compilation_cache_dir)
    clock = CompileClock()
    try:
        run_phases(args, clock)
    finally:
        clock.close()

    if not on_tpu or args.reduced:
        print("phases passed, but not a full-size TPU run: no ok line", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


def run_phases(args, clock: CompileClock) -> None:
    import jax

    train_args = TRAIN_REDUCED if args.reduced else TRAIN
    if args.four_chips:
        if len(jax.devices()) < 4:
            raise RuntimeError(f"--four-chips needs 4 devices, JAX found {len(jax.devices())}")
        state, one = train_phase(train_args + ["--steps", "3"], "train one chip", clock)
        del state
        gc.collect()
        state, mesh = train_phase(train_args + ["--steps", "3", "--mesh", "2x2"],
                                  "train 2x2 mesh", clock)
        check_spread(state["params"])
        diff = [abs(a - b) for a, b in zip(one, mesh)]
        fact("mesh check", loss_abs_diff=diff, atol=LOSS_ATOL)
        if not max(diff) <= LOSS_ATOL:
            raise AssertionError(f"mesh losses differ from one chip: {diff}")
    else:
        serve_phase(args.reduced, clock)
        gc.collect()
        train_phase(train_args + ["--steps", "5"], "train", clock)
    # all of the runtime's memory counters, to read the peaks above against
    fact("device memory", **(jax.devices()[0].memory_stats() or {}))


if __name__ == "__main__":
    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
