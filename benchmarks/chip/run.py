"""Run one cell of the chip benchmark once, and print its result line.

  python benchmarks/chip/run.py --workload starcoder2-3b.decode --seed 7 --seconds 30 --trace 0

The cell, its configuration and its end-to-end and per-layer metrics are
entries of `BENCHMARK.json` at the checkout's root; everything that belongs to
one configuration, traffic mix, metric or cell is a file of its own here,
found by its name:

  configs/<config>.json    the sizes as run, with source, cuts and departures, and
                           the family it names (`"reference"`); a section of the
                           program's config as a mapping of its fields
                           (`"moe": {"num_experts": 8, ...}`), `null` for none
  families/<family>.py     what is specific to one architecture: the sizes it
                           covers, its parameter tree, its plain reference, its
                           work counts and its named scopes (`family.py`)
  traffic/<traffic>.json   the mix's parameters, read by `traffic.py`
  metrics/<metric>.py      a per-layer metric's reader: read(run) -> value or None
  limits/<cell>.json       the limit of each number the correctness check compares

With `--trace 0` the result holds the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics: the device's from the profiler, on over the
first few seconds of the window, the host clock's from the rest. The last line of standard output is one JSON
object; the numbers compared with the reference, each beside its limit, are
the last lines of standard error and the result's last key. Off a TPU, or
with fewer chips than the cell asks for, the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import fields, is_dataclass, replace  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import family  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def entry(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"BENCHMARK.json has no {what} {name!r}")


def applies(metric: dict, cell: str, e2e_names=None) -> bool:
    """A metric is reported in the cells it lists; without a list, in every
    cell (a per-layer one: every cell that reports what it moves)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def model_config(cfg: dict, fam):
    """The program's config for the file's arch, with every size of the file,
    a section (`family.sections`) applied field by field onto the arch's own;
    refused where the family `fam` does not cover it."""
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    sizes = {k: v for k, v in cfg.items() if k not in family.META}
    unknown = [k for k in sizes if not hasattr(base, k)]
    if unknown:
        raise KeyError(f"configuration keys the program does not have: {unknown}")
    for name, given in family.sections(cfg).items():
        own = getattr(base, name)
        if not is_dataclass(own):
            raise KeyError(f"configuration section {name!r}: the program config of "
                           f"{cfg['arch']!r} has no such section ({name} = {own!r})")
        unknown = [k for k in given if k not in {f.name for f in fields(own)}]
        if unknown:
            raise KeyError(f"configuration section {name!r}: fields the program does not "
                           f"have: {unknown}")
        sizes[name] = replace(own, **given)
    mcfg = replace(base, **sizes)
    off = {k: getattr(mcfg, k) for k, v in fam.COVERS.items() if getattr(mcfg, k) != v}
    if off:
        raise ValueError(f"family {cfg['reference']!r} does not cover {off}")
    return mcfg


def load_reader(base: Path, name: str):
    return family.load_file(base / "metrics" / f"{name}.py", f"metric_{name}").read


class CompileClock:
    """Seconds of XLA backend compilation, as JAX reports them."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.seconds, self.count = 0.0, 0

        def listen(event, secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.seconds += secs
                self.count += 1

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


class Tracer:
    """The profiler over the start of the window, when asked for."""

    def __init__(self, on: bool):
        self.on, self.dir, self.running = on, None, False

    def start(self):
        import jax

        if self.on:
            self.dir = tempfile.mkdtemp(prefix="chip_bench_trace_")
            jax.profiler.start_trace(self.dir)
            self.running = True

    def stop(self):
        import jax

        if self.running:
            jax.profiler.stop_trace()
            self.running = False


def enable_compile_cache(root: Path) -> str:
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def peak_memory(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def reduce_scopes(xplane: str, hlo, fam) -> dict | None:
    """`scopes.reduce_xplane` of the trace by the family's named scopes; a
    failure is logged and reads None, so that only the readers of scopes lose."""
    import scopes

    t0 = time.perf_counter()
    try:
        got = scopes.reduce_xplane(xplane, scopes.scope_map(hlo or [], fam.SCOPES))
    except Exception as e:  # the run still prints its line, without the scope readers
        log(f"scopes: the reduction failed: {type(e).__name__}: {e}")
        return None
    if got is None:
        log("scopes: no annotated window or no device operation in the trace")
        return None
    runs = {n: p["runs"] for n, p in got["programs"].items()}
    log(f"scopes: reduced in {time.perf_counter() - t0:.3f} s; clock offset "
        f"{got['clock_offset_ms']:.4f} ms, bounds {got['clock_bounds_ms']} ms; runs {runs}")
    return got


def main(argv=None, *, root: Path = ROOT, base: Path = HERE, require_tpu: bool = True,
         compile_cache: bool = True, t0: float = T0) -> int:
    """Run the cell; `root` holds BENCHMARK.json, `base` the harness's data
    files. Tests pass `require_tpu=False` and `compile_cache=False`."""
    args = parse(argv)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = entry(bench["workloads"], args.workload, "workload")
    cfg_entry = entry(bench["configs"], cell["config"], "config")
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    import traffic

    fam = family.load(base, cfg)
    mix = traffic.load(base, cell["traffic"])
    limits_path = base / "limits" / f"{cell['name']}.json"
    limits = json.loads(limits_path.read_text()) if limits_path.exists() else {}

    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        log(f"needs {cell['chips']} TPU chip(s); JAX found {len(devices)} {devices[0].platform} "
            f"device(s) ({kind})")
        return 3
    import peaks

    peak = peaks.peaks(kind) if require_tpu else peaks.PEAKS.get(kind)
    cache = enable_compile_cache(root) if compile_cache else "off"
    sys.path.insert(0, str(ROOT / "src"))
    mcfg = model_config(cfg, fam)
    log(f"cell {cell['name']}: {cfg['arch']} ({cfg['reference']} family), {cfg['num_layers']} "
        f"layers, traffic {mix}, device {kind} x{len(devices)}, compile cache {cache}")
    if "compile_rehearsal" in cfg:
        log("compile rehearsal (described v5e, before any chip run):", cfg["compile_rehearsal"])

    clock = CompileClock()
    tracer = Tracer(bool(args.trace))
    marks: dict = {}

    def setup_done():
        marks["setup_s"] = time.perf_counter() - t0
        marks["compile_s"], marks["compiles0"] = clock.seconds, clock.count
        tracer.start()

    def window_done():
        tracer.stop()
        marks["window_compiles"] = clock.count - marks["compiles0"]
        marks["memory_peak_bytes"] = peak_memory(devices[: cell["chips"]])
        log("peak_bytes_in_use after the window:", marks["memory_peak_bytes"])

    def predict(fn):
        try:
            return fn()
        except Exception as e:  # the analytical model is printed, never relied on
            return f"failed: {type(e).__name__}: {e}"

    import serving

    ctx = {"config": cfg, "mix": mix, "seed": args.seed, "seconds": args.seconds,
           "model_config": mcfg, "family": fam, "tracer": tracer, "setup_done": setup_done,
           "window_done": window_done, "predict": predict}
    try:
        out = serving.run(ctx)
    finally:
        tracer.stop()
        clock.close()
    log(f"setup_s {marks['setup_s']:.3f} (compile {marks['compile_s']:.3f} s), "
        f"compiles inside the window: {marks['window_compiles']}")

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": marks["memory_peak_bytes"]}
    result = {"correct": False, "attempted": out["attempted"], "failed": out["failed"]}
    e2e_names = [m["name"] for m in bench["end_to_end"] if applies(m, cell["name"])]
    values = {"setup_s": marks["setup_s"], **out["e2e"]}
    if not args.trace:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"] if m["name"] in e2e_names}
    else:
        record = {**out["record"], "compile_s": marks["compile_s"], "chips": cell["chips"],
                  "config": cfg, "mix": mix, "peak": peak, "trace": None, "scopes": None}
        hlo = record.pop("hlo", None)
        if tracer.dir:
            import devtrace

            xplane = devtrace.find_xplane(tracer.dir)
            record["trace"] = devtrace.reduce_xplane(xplane)
            record["scopes"] = reduce_scopes(xplane, hlo, fam)
            shutil.rmtree(tracer.dir, ignore_errors=True)
        metrics = {}
        for m in bench["per_layer"]:
            if applies(m, cell["name"], e2e_names):
                v = load_reader(base, m["name"])(record)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        tr = record["trace"]
        if tr:
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device

    checks = {name: {"value": v, "limit": limits.get(name, {}).get("limit")}
              for name, v in out["checks"].items()}
    ok = out["failed"] == 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"] for c in checks.values())
    for c in checks.values():  # strict JSON has no NaN or Infinity
        c["value"] = c["value"] if math.isfinite(c["value"]) else str(c["value"])
    result["correct"] = bool(ok)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
