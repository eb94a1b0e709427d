"""Serving launcher: batched generation with the slot-based engine.

  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --slots 8 \
      --requests 8 --prompt-len 1024 --max-new 64 --max-len 2048
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced

Runs the published config (random weights from a fixed seed) unless
`--reduced` asks for the tiny same-family config. Warms up once at the
request shapes, so the reported serve time holds no compilation.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.runtime import device_info, enable_compile_cache
from repro.models.transformer import Model
from repro.serve.engine import Request, ServeEngine


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config instead of the published one")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    return ap


def main(argv: list[str] | None = None) -> tuple[ServeEngine, list[Request]]:
    """Serve `--requests` random prompts; returns the engine and the requests."""
    args = build_parser().parse_args(argv)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_len=args.max_len, slots=args.slots)

    # one request at the prompt length compiles prefill and decode: both run
    # at the full slot batch whatever the number admitted
    t0 = time.perf_counter()
    engine.serve([Request(prompt=np.zeros(args.prompt_len, np.int32), max_new_tokens=2)])
    warm = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32),
                max_new_tokens=args.max_new)
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    done = engine.serve(reqs)
    dt = time.perf_counter() - t0
    if not all(r.done for r in done):
        raise RuntimeError("serve() returned unfinished requests")
    n_tok = sum(len(r.out_tokens) for r in done)
    print(f"{cfg.name} ({cfg.num_layers} layers{', reduced' if args.reduced else ''}) "
          f"on {device_info()}")
    print(f"warm-up (compile, one prefill, one decode) {warm:.2f}s")
    print(f"served {len(done)} requests, {n_tok} tokens in {dt:.2f}s: "
          f"prefill {engine.prefill_s:.3f}s, {engine.decode_steps} decode steps "
          f"in {engine.decode_s:.3f}s; host: sample {engine.sample_s:.3f}s, "
          f"dispatch {engine.dispatch_s:.3f}s")
    return engine, done


if __name__ == "__main__":
    enable_compile_cache()
    main()
