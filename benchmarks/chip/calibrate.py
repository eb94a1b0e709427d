"""Read the numbers a cell's correctness check compares, on many seeds in one
process: the program's (sound runs), the control's and those of planted faults.

  python benchmarks/chip/calibrate.py --workload starcoder2-3b.decode --seeds 11,12,13 \\
      --out calibrate.json

The limits in `limits/<cell>.json` are set from these readings: above the
largest that sound runs of the program give, below the smallest that the
control gives. Each seed runs the cell's own load, as many whole waves of the
mix as it takes to hold `check_requests` requests, and reads `logit_gap` over a
sample of as many requests as a run compares.

- control: the reference of the family the configuration names
  (`families/<name>.py`) in float8, mode "fp8", in the program's place: at
  each position of the same prompts and served tokens, the gap of the token
  the control puts first.
- faults, on the first `FAULT_SEEDS` seeds: `altered`, a served token
  replaced by the next id, read at the first served position; `stale_cache`,
  a decode step that returns the cache it was given.

Off a TPU it refuses (exit 3).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import run as harness

FAULT_SEEDS = 3


def serve_readings(ctx, seeds, log):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import traffic
    import weights
    from repro.models.transformer import Model
    from repro.serve.engine import Request, ServeEngine

    cfg, mix, fam = ctx["config"], ctx["mix"], ctx["family"]
    S, B, new, V, k = (mix["prompt_len"], mix["slots"], mix["new_tokens"], cfg["vocab_size"],
                       mix["check_requests"])
    model = Model(ctx["model_config"])
    engine = ServeEngine(model, None, max_len=mix["max_len"], slots=B)
    decode = engine._decode
    waves = -(-k // B)  # enough whole waves to sample as many requests as a run compares
    stale = jax.jit(lambda p, c, t: (model.decode_step(p, c, t)[0], c))
    out = []
    for seed in seeds:
        row = {"seed": seed}
        runs = (("program", decode), ("stale_cache", stale))
        for fault, dec in runs[: 2 if len(out) < FAULT_SEEDS else 1]:
            engine._decode = dec
            engine.params = weights.make(fam, cfg, seed)
            reqs = []
            t0 = time.perf_counter()
            for w in range(waves):
                wave = [Request(prompt=p, max_new_tokens=new)
                        for p in traffic.prompts(seed, w, B, S, V, mix.get("topic_ids"))]
                engine.serve(wave)
                reqs += wave
            wave_s = (time.perf_counter() - t0) / waves
            engine.params = None  # the reference runs with the program's weights freed
            gc.collect()
            finished = [(r.prompt, list(r.out_tokens), r.done) for r in reqs]
            pick = traffic.check_sample(seed, len(finished), k)
            seqs = np.stack([np.concatenate([finished[i][0], finished[i][1][:-1]]) for i in pick])
            served = jnp.asarray(np.stack([finished[i][1] for i in pick]))
            t0 = time.perf_counter()
            ref = fam.scored_logits(cfg, seed, seqs, S - 1)
            best = ref.max(-1).block_until_ready()
            ref_s = time.perf_counter() - t0

            def gap(tok):
                return best - jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]

            if fault == "program":
                row.update(program=float(gap(served).max()), wave_s=wave_s, ref_s=ref_s,
                           altered=float(gap((served + 1) % V)[:, 0].min()))
                ctl = fam.scored_logits(cfg, seed, seqs, S - 1, mode="fp8")
                row["control"] = float(gap(jnp.argmax(ctl, -1)).max())
                del ctl
            else:
                row[fault] = float(gap(served).max())
            del ref, best
        engine._decode = decode
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root, base = harness.ROOT, harness.HERE
    seeds = [int(s) for s in args.seeds.split(",")]

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = harness.entry(bench["workloads"], args.workload, "workload")
    cfg = json.loads((root / harness.entry(bench["configs"], cell["config"], "config")["file"]).read_text())
    import jax

    import family
    import traffic

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        harness.log(f"no TPU: JAX found {dev.platform}")
        return 3
    harness.enable_compile_cache(root)
    sys.path.insert(0, str(harness.ROOT / "src"))
    mix = traffic.load(base, cell["traffic"])
    fam = family.load(base, cfg)
    ctx = {"config": cfg, "mix": mix, "family": fam,
           "model_config": harness.model_config(cfg, fam)}
    rows = serve_readings(ctx, seeds, harness.log)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({"workload": args.workload, "device": dev.device_kind,
                                          "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
