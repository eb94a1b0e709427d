"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b --layers 4 \
      --batch 2 --seq 4096 --steps 5
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --reduced \
      --steps 50 --checkpoint-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch gpt-175b --auto-plan \
      --chips 1024 --batch 1024   # analytic planning only (no execution)

Trains the published config, with random initial weights and synthetic data,
unless `--reduced` asks for the tiny same-family config. `--layers N` keeps
every width, head count and the vocabulary as published and cuts only the
depth, to what one chip holds. `--mesh 2x2` shards over a (data, model) mesh.
"""

from __future__ import annotations

import argparse
import dataclasses

from repro.configs import get_config
from repro.configs.base import ParallelConfig, TrainConfig
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.runtime import device_info, enable_compile_cache
from repro.models.transformer import Model
from repro.train.trainer import Trainer


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config instead of the published one")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers, every width as published")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="selective", choices=["none", "selective", "full"])
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adamw8bit"])
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="", help="e.g. 2x4 -> (data, model) axes")
    # analytic planning path
    ap.add_argument("--auto-plan", action="store_true")
    ap.add_argument("--chips", type=int, default=256)
    ap.add_argument("--hardware", default="tpu-v5e")
    return ap


def main(argv: list[str] | None = None):
    """Train; returns (final state, per-step history), or None for --auto-plan."""
    args = build_parser().parse_args(argv)

    if args.auto_plan:
        from repro.core.hardware import get_hardware
        from repro.core.paper_data import GPT_CONFIGS, LLAMA2_CONFIGS
        from repro.core.planner import plan

        cfg = (GPT_CONFIGS.get(args.arch) or LLAMA2_CONFIGS.get(args.arch)
               or get_config(args.arch))
        hw = get_hardware(args.hardware)
        print(f"auto-plan: {cfg.name} on {args.chips} x {hw.name}, batch {args.batch}")
        for p in plan(cfg, hw, args.chips, global_batch=args.batch, seq=args.seq,
                      max_tp=64):
            print(" ", p.describe())
        return None

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    model = Model(cfg)
    print(f"{cfg.name} ({cfg.num_layers} layers, {model.param_count() / 1e9:.2f}B params"
          f"{', reduced' if args.reduced else ''}) on {device_info()}"
          + (f", mesh {args.mesh}" if args.mesh else ""))

    mesh = rules = None
    pcfg = ParallelConfig(remat=args.remat, microbatches=args.microbatches,
                          grad_compress=args.grad_compress)
    if args.mesh:
        from repro.launch.mesh import make_mesh
        from repro.parallel.axes import make_rules

        shape = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[: len(shape)] if len(shape) == 2 else ("pod", "data", "model")
        mesh = make_mesh(shape, axes)
        rules = make_rules(dp=tuple(a for a in axes if a != "model"), tp=("model",))

    tcfg = TrainConfig(steps=args.steps, optimizer=args.optimizer,
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_every=args.checkpoint_every)
    trainer = Trainer(model, pcfg, tcfg, mesh=mesh, rules=rules)

    start = 0
    if args.resume and args.checkpoint_dir:
        try:
            state, start = trainer.resume()
            print(f"resumed from step {start}")
        except FileNotFoundError:
            state = trainer.init_state()
    else:
        state = trainer.init_state()

    data = Prefetcher(iter(SyntheticLM(cfg.vocab_size, args.seq, args.batch)))
    # skip already-consumed steps for deterministic resume
    for _ in range(start):
        next(data)
    state, history = trainer.fit(state, data, steps=args.steps - start, start_step=start)
    print(f"done: final loss {history[-1]['loss']:.4f}, "
          f"straggler steps {history[-1]['slow_steps']}")
    return state, history


if __name__ == "__main__":
    enable_compile_cache()
    main()
