"""Serving cells: closed-loop waves through `ServeEngine.serve`.

Set-up draws the weights from the seed and serves one request of the cell's
prompt length with 2 new tokens, which compiles prefill and decode at the full
slot batch. The window then serves whole waves until `--seconds` have passed;
it is the exact span of the waves it holds. A request is due when its wave's
`serve()` call starts; its first token reaches the host when that call's
prefill returns (`engine.prefill_s` later). With `--trace 1` the profiler runs
over the first whole waves of `TRACE_SECONDS` or more; each wave records
whether it was traced, so that host-clock readers can leave those out.

Each wave's record holds every public number the engine holds after its
`serve()` (`counters`): `prefill_s`, `decode_s`, `decode_steps`, `sample_s`,
`dispatch_s` and whatever counter the program adds, so that a new
`metrics/<name>.py` reads a new counter with no edit here; beside them the
harness's own `t0`, `t1`, `traced` and `tokens`, which win over a counter of
the same name.

After the window the program's state is freed and the plain reference of the
configuration's family (`families/<name>.py`) reads a sample of the finished
requests drawn from the seed: for each served token, how far its logit lies
below the reference's best at that position. A `--trace 1` run also keeps the
compiled HLO text of the programs that `memory_analysis` compiles after the
window, from which `run.py` maps device time to the family's named scopes.
"""

from __future__ import annotations

import gc
import numbers
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import traffic
import weights

TRACE_SECONDS = 5.0  # whole waves traced at the start of a --trace 1 window


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def prediction(mcfg, mix) -> dict:
    from repro.core.hardware import TPU_V5E
    from repro.core.predict import inference_latency

    b = inference_latency(mcfg, TPU_V5E, tp=1, batch=mix["slots"], prompt=mix["prompt_len"],
                          gen=mix["new_tokens"])
    return {"ttft_ms": b.ttft * 1e3, "tpot_ms": b.tpot * 1e3, "wave_s": b.total,
            "gen_tokens_per_s": mix["slots"] * mix["new_tokens"] / b.total}


def counters(engine) -> dict:
    """Every public int or float attribute of `engine`."""
    return {k: v for k, v in vars(engine).items()
            if not k.startswith("_") and isinstance(v, numbers.Real) and not isinstance(v, bool)}


def memory_analysis(engine, model, mix, with_text: bool = False) -> tuple[dict, list[str]]:
    """What XLA reserves for each of the engine's programs, where they can be
    lowered, and with `with_text` their compiled HLO text."""
    S, B = mix["prompt_len"], mix["slots"]
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), engine.params)
    args = {
        "_prefill": (params, {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}),
        "_decode": (params, model.cache_shapes(B, mix["max_len"]),
                    jax.ShapeDtypeStruct((B, 1), jnp.int32)),
    }
    out, texts = {}, []
    for name, a in args.items():
        fn = getattr(engine, name, None)
        if not hasattr(fn, "lower"):
            out[name] = "not a jitted function"
            continue
        compiled = fn.lower(*a).compile()
        if with_text:
            texts.append(compiled.as_text())
        m = compiled.memory_analysis()
        out[name] = {k: getattr(m, k) for k in ("argument_size_in_bytes", "output_size_in_bytes",
                                                 "temp_size_in_bytes", "generated_code_size_in_bytes")}
    return out, texts


def run(ctx) -> dict:
    from repro.models.transformer import Model
    from repro.serve.engine import Request, ServeEngine

    cfg, mix, seed, fam = ctx["config"], ctx["mix"], ctx["seed"], ctx["family"]
    S, B, new, V = mix["prompt_len"], mix["slots"], mix["new_tokens"], cfg["vocab_size"]
    log("prediction (repro.core, tpu-v5e):", ctx["predict"](lambda: prediction(ctx["model_config"], mix)))

    t0 = time.perf_counter()
    model = Model(ctx["model_config"])
    weights.check_tree(fam, cfg, model.pshapes())
    params = jax.block_until_ready(weights.make(fam, cfg, seed))
    t1 = time.perf_counter()
    engine = ServeEngine(model, params, max_len=mix["max_len"], slots=B)
    del params
    engine.serve([Request(prompt=np.zeros(S, np.int32), max_new_tokens=2)])
    t2 = time.perf_counter()
    # set-up's tracing leaves many long-lived objects: a full collection inside
    # the window then walks only the window's own
    gc.collect()
    gc.freeze()
    log(f"set-up: weights {t1 - t0:.3f} s, engine and warm-up {t2 - t1:.3f} s, "
        f"collection {time.perf_counter() - t2:.3f} s, {gc.get_freeze_count()} objects frozen")
    ctx["setup_done"]()

    waves, finished = [], []
    tracer = ctx["tracer"]
    w0, cpu0 = time.perf_counter(), time.process_time()
    while True:
        reqs = [Request(prompt=p, max_new_tokens=new)
                for p in traffic.prompts(seed, len(waves), B, S, V, mix.get("topic_ids"))]
        traced = tracer.running
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("wave"):
            engine.serve(reqs)
        t1 = time.perf_counter()
        waves.append({**counters(engine), "t0": t0, "t1": t1, "traced": traced,
                      "tokens": [len(r.out_tokens) for r in reqs]})
        finished += [(r.prompt, list(r.out_tokens), r.done) for r in reqs]
        log(f"wave {len(waves) - 1}: {t1 - t0:.4f} s, prefill {engine.prefill_s:.4f} s, "
            f"decode {engine.decode_s:.4f} s over {engine.decode_steps} steps"
            f"{', traced' if traced else ''}")
        if t1 - w0 >= TRACE_SECONDS:
            tracer.stop()
        if t1 - w0 >= ctx["seconds"]:
            break
    ctx["window_done"]()
    log(f"host CPU in the window: {time.process_time() - cpu0:.3f} s "
        f"over {time.perf_counter() - w0:.3f} s")

    keep_hlo = ctx["tracer"].on
    analysis, hlo = memory_analysis(engine, model, mix, with_text=keep_hlo)
    log("memory_analysis:", analysis)
    del engine
    gc.unfreeze()
    gc.collect()

    failed = sum(1 for _, out, done in finished if not done or len(out) != new)
    window = waves[-1]["t1"] - waves[0]["t0"]
    e2e = {
        "gen_tokens_per_s": sum(sum(w["tokens"]) for w in waves) / window,
        "ttft_p95_ms": float(np.percentile(request_ms(waves)[0], 95)),
    }
    log(f"window: {len(waves)} waves, {len(finished)} requests, {window:.3f} s")

    t0 = time.perf_counter()
    checks = {"logit_gap": served_gap(fam, cfg, seed, finished, S, new, mix["check_requests"])}
    log(f"reference: {mix['check_requests']} requests compared in {time.perf_counter() - t0:.3f} s")
    record = {"waves": waves, "prompt_len": S, "slots": B}
    if keep_hlo:
        record["hlo"] = hlo
    return {"e2e": e2e, "checks": checks, "attempted": len(finished), "failed": failed,
            "record": record}


def request_ms(waves) -> tuple[list[float], list[float]]:
    """Each request's milliseconds to its first token and per later token. A
    request is due at its wave's `serve()` call, and shares its wave's
    prefill and decode: (return - first token) / (tokens - 1)."""
    ttft, tpot = [], []
    for w in waves:
        first = w["prefill_s"]
        for n in w["tokens"]:
            ttft.append(first * 1e3)
            tpot.append((w["t1"] - w["t0"] - first) / max(n - 1, 1) * 1e3)
    return ttft, tpot


def served_gap(fam, cfg, seed, finished, S, new, k) -> float:
    """Widest gap, over a seeded sample of finished requests, by which a served
    token's logit lies below the reference's best at its position."""
    pick = [i for i in traffic.check_sample(seed, len(finished), k) if len(finished[i][1]) == new]
    if not pick:
        return float("inf")
    seqs = np.stack([np.concatenate([finished[i][0], finished[i][1][:-1]]) for i in pick])
    served = jnp.asarray(np.stack([finished[i][1] for i in pick]))
    ref = fam.scored_logits(cfg, seed, seqs, S - 1)
    gap = ref.max(-1) - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    return float(gap.max())
