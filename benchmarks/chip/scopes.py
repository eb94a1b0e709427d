"""Device time by program and named scope, and idle time by engine span.

Three reductions of a profiler trace (`.xplane.pb`) of the harness's `wave`
window, beside `devtrace.py`'s whole-window numbers:

- scope map: from each compiled program's HLO text, the named scope of every
  instruction (`%fusion.4` -> `attn/kv_write`): the deepest of `SCOPES` and,
  under `attn`, of `ATTN_PARTS` in its `metadata={op_name=...}`, else
  `unscoped`;
- device time: the device's `XLA Modules` line gives each program execution,
  and each operation of the `XLA Ops` line is given to the execution that
  holds it, so that `%fusion.4` of `jit_prefill` and of `jit_decode` stay
  apart; seconds by (program, scope), each instant of the window in which
  the device is busy given to the innermost operation running then (`own`),
  so that a `while` and its body count once and the scopes sum to the busy
  time;
- idle time: each stretch of the window in which the device runs nothing,
  labelled by the innermost `engine.*` host span covering its middle, or
  `outside engine`.

The device's clock and the host's differ by a small offset. A decode program
cannot start before its `engine.dispatch` span starts, nor end after its
`engine.fetch` span ends; over all decode steps those two facts bound the
offset, and the midpoint of the bounds is taken. Device times are moved onto
the host's clock by it before they are clipped and labelled. Bounds that
cross, or decode programs that do not pair one to one with their spans,
leave the offset at 0, and the reduction says so.

Seconds are averaged over the devices. With no engine span or named program
in the trace (a program without them) the reduction still runs.

`run.py` does not call this module yet: it reduces the trace with
`devtrace.py` alone and removes it before the readers run. The tests call it,
on synthetic events and on the engine trace that
`tests/chip_bench/record_engine_trace.py` records on the chip.
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict

import devtrace

SCOPES = ("embed", "norm", "attn", "mlp", "head")
ATTN_PARTS = ("qkv", "kv_write", "core", "out")
UNSCOPED = "unscoped"
OUTSIDE = "outside engine"
SPAN = "engine."
MODULES_LINE = "XLA Modules"
# the decode program and the spans that bound it on the host
DECODE, DISPATCH, FETCH = "jit_decode", "engine.dispatch", "engine.fetch"

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_MODULE_START = re.compile(r"^(?=HloModule\s)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=(.*)$", re.M)
_OP_NAME = re.compile(r"op_name=\"((?:[^\"\\]|\\.)*)\"")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def scope_of(op_name: str) -> str:
    """`jit(decode)/while/body/closed_call/attn/kv_write/dynamic_update_slice`
    -> `attn/kv_write`; a name with none of the scopes -> `unscoped`."""
    scope = UNSCOPED
    for part in op_name.split("/"):
        if part in SCOPES:
            scope = part
        elif part in ATTN_PARTS and scope.startswith("attn"):
            scope = "attn/" + part
    return scope


def scope_map(hlo_texts) -> dict:
    """{module name: {"%instruction": scope}} from compiled HLO texts, each
    holding one module or more; an instruction without metadata is
    `unscoped`."""
    out: dict = {}
    for text in hlo_texts:
        for module in _MODULE_START.split(text):
            m = _MODULE.search(module)
            if m:
                names = out.setdefault(m.group(1), {})
                for name, rest in _INSTR.findall(module):
                    op_name = _OP_NAME.search(rest)
                    names["%" + name] = scope_of(op_name.group(1)) if op_name else UNSCOPED
    return out


def compiled_texts(engine, model, mix) -> list[str]:
    """The compiled HLO text of the engine's two programs at the mix's shapes
    (`slots`, `prompt_len`, `max_len`), lowered as `serving.memory_analysis`
    lowers them; `scope_map` reads it."""
    import jax
    import jax.numpy as jnp

    B, S = mix["slots"], mix["prompt_len"]
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), model.pshapes())
    tokens = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    step = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    return [engine._prefill.lower(params, tokens).compile().as_text(),
            engine._decode.lower(params, model.cache_shapes(B, mix["max_len"]), step)
            .compile().as_text()]


def module_name(event_name: str) -> str:
    """`jit_decode(12082329257286557678)` -> `jit_decode`."""
    return event_name.split("(", 1)[0]


def clock_offset(host, per_device):
    """(offset ns, (low, high) ns or None): device time minus host time, from
    each decode program's execution against its `engine.dispatch` start and
    `engine.fetch` end, on every device. `per_device`: for each device, its
    program executions as (name, start, end) on its clock."""
    starts = sorted(s for n, s, _ in host if n == DISPATCH)
    ends = sorted(e for n, _, e in host if n == FETCH)
    lows, highs = [], []
    for modules in per_device:
        runs = sorted((s, e) for n, s, e in modules if module_name(n) == DECODE)
        if not runs:
            continue
        if not (len(runs) == len(starts) == len(ends)):
            log(f"scopes: {len(runs)} decode programs against {len(starts)} dispatch and "
                f"{len(ends)} fetch spans; clock offset left at 0")
            return 0.0, None
        lows.append(max(e - fe for (_, e), fe in zip(runs, ends)))
        highs.append(min(s - ds for (s, _), ds in zip(runs, starts)))
    if not lows:
        return 0.0, None
    low, high = max(lows), min(highs)
    if low > high:
        log(f"scopes: clock offset bounds cross ({low / 1e6:.4f} > {high / 1e6:.4f} ms); "
            "left at 0")
        return 0.0, (low, high)
    return (low + high) / 2, (low, high)


def own(events):
    """(name, start, end) of each stretch of time that an event holds on its
    own: every instant covered by events goes to the innermost one covering
    it, the one that started last, so the stretches sum to the events'
    union. Counting leaf events only would drop the time of an operation that
    a short event starts inside without being its child (an asynchronous
    copy on the same line), and a loop's own time beyond its body."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    points = sorted({t for _, s, e in evs for t in (s, e)})
    out, stack, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i][1] <= a:
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            out.append((stack[-1][0], a, b))
    return out


def reduce_xplane(path: str, smap: dict, annotations=devtrace.ANNOTATIONS) -> dict | None:
    """`reduce_events` of the trace at `path`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: list(devtrace._events(ln)) for ln in plane.lines}
            if lines.get(devtrace.OPS_LINE):
                devices.append((plane.name, lines.get(MODULES_LINE, []),
                                lines[devtrace.OPS_LINE]))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = list(devtrace._events(ln))
                if any(n in annotations for n, _, _ in evs):
                    host = evs
    return reduce_events(host, devices, smap, annotations)


def reduce_events(host, devices, smap, annotations=devtrace.ANNOTATIONS) -> dict | None:
    """The reduction itself. `host`: (name, start_ns, end_ns) events of the host
    thread that holds the annotations; `devices`: (plane name, program
    executions, operations), each a list of (name, start_ns, end_ns) on the
    device's clock; `smap`: `scope_map`'s output. None when the trace holds no
    annotated window or no device operation."""
    spans = [(s, e) for n, s, e in host if n in annotations]
    if not spans or not any(ops for _, _, ops in devices):
        return None
    w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    engine = [(n, s, e) for n, s, e in host if n.startswith(SPAN)]
    offset, bounds = clock_offset(host, [mods for _, mods, _ in devices])

    seconds = defaultdict(lambda: defaultdict(float))
    unmatched, busy, idle = defaultdict(float), defaultdict(float), defaultdict(float)
    runs = defaultdict(int)
    for _, mods, ops in devices:
        mods = sorted((s - offset, e - offset, module_name(n)) for n, s, e in mods)
        clipped = [(n, max(s - offset, w0), min(e - offset, w1)) for n, s, e in ops
                   if e - offset > w0 and s - offset < w1]
        for s, _, name in mods:
            runs[name] += w0 <= s < w1
        by_module = defaultdict(list)
        j = 0
        for ev in sorted(clipped, key=lambda ev: ev[1]):
            while j < len(mods) and mods[j][1] <= ev[1]:
                j += 1
            by_module[mods[j][2] if j < len(mods) and mods[j][0] <= ev[1] else "no program"] \
                .append(ev)
        for name, evs in by_module.items():
            busy[name] += devtrace.length(devtrace.union((s, e) for _, s, e in evs)) / 1e9
            names = smap.get(name, {})
            for n, s, e in own(evs):
                op = devtrace.short(n)
                seconds[name][names.get(op, UNSCOPED)] += (e - s) / 1e9
                if op not in names:
                    unmatched[name] += (e - s) / 1e9
        merged = devtrace.union((s, e) for _, s, e in clipped)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                covering = [(he - hs, n) for n, hs, he in engine if hs <= mid <= he]
                idle[min(covering)[1] if covering else OUTSIDE] += (e - s) / 1e9

    n = len(devices)
    counts = defaultdict(int)
    for name, s, _ in engine:
        counts[name] += w0 <= s < w1
    return {
        "window_s": (w1 - w0) / 1e9,
        "clock_offset_ms": offset / 1e6,
        "clock_bounds_ms": None if bounds is None else [bounds[0] / 1e6, bounds[1] / 1e6],
        "programs": {name: {"runs": runs[name] // n,
                            "busy_s": busy[name] / n, "unmatched_s": unmatched[name] / n,
                            "scopes": {k: v / n for k, v in sorted(sc.items(),
                                                                   key=lambda kv: -kv[1])}}
                     for name, sc in seconds.items()},
        "idle": {k: v / n for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "spans": dict(counts),
    }
