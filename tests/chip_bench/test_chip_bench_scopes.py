"""The reduction from a profiler trace to device time by program and named
scope and to idle time by engine span (`scopes.py`), on synthetic events and
on a trace of the engine recorded on the chip."""

import gzip
import re
from pathlib import Path

import pytest

import scopes

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def test_scope_of_takes_the_deepest_named_scope():
    assert scopes.scope_of("jit(decode)/while/body/closed_call/attn/kv_write/"
                           "dynamic_update_slice") == "attn/kv_write"
    assert scopes.scope_of("jit(prefill)/while/body/closed_call/mlp/dot_general") == "mlp"
    assert scopes.scope_of("jit(decode)/while/body/closed_call/attn/convert") == "attn"
    assert scopes.scope_of("attn/core/reduce_max") == "attn/core"
    # a part of attention counts only inside `attn`, and the scan's own work in none
    assert scopes.scope_of("jit(decode)/core/add") == scopes.UNSCOPED
    assert scopes.scope_of("jit(decode)/while/body/dynamic_update_slice") == scopes.UNSCOPED


def test_scope_map_reads_compiled_hlo_text():
    text = "\n".join([
        "HloModule jit_decode, is_scheduled=true, entry_computation_layout={()->()}",
        "",
        "ENTRY %main.17 (p: bf16[8]) -> bf16[8] {",
        '  %fusion.4 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fused.1, '
        'metadata={op_name="jit(decode)/while/body/closed_call/attn/kv_write/'
        'dynamic_update_slice" stack_frame_id=8}',
        '  ROOT copy.7 = bf16[8]{0} copy(%fusion.4), metadata={op_name="c[\\\'pos\\\']"}',
        "  %copy.8 = bf16[8]{0} copy(%copy.7)",
        "}",
    ])
    assert scopes.scope_map([text]) == {
        "jit_decode": {"%fusion.4": "attn/kv_write", "%copy.7": scopes.UNSCOPED,
                       "%copy.8": scopes.UNSCOPED}}


SMAP = {"jit_prefill": {"%fusion.4": "mlp", "%while.1": "attn/core", "%fusion.7": "attn/core",
                        "%fusion.8": "attn/core"},
        "jit_decode": {"%fusion.4": "attn/kv_write", "%fusion.5": "attn/core"}}


def decode_step(t, offset=0, run=(1, 5), fetch_end=6):
    """Host spans of one decode step from `t` ms, and its program's execution
    from `t + run[0]` to `t + run[1]` ms on a device clock `offset` ms ahead."""
    host = [("engine.decode", t * MS, (t + fetch_end) * MS),
            ("engine.dispatch", t * MS, (t + 0.5) * MS),
            ("engine.fetch", (t + 0.5) * MS, (t + fetch_end) * MS)]
    prog = ("jit_decode(123)", (t + run[0] + offset) * MS, (t + run[1] + offset) * MS)
    return host, prog


def test_own_time_goes_to_the_innermost_operation():
    evs = [("while", 0, 10), ("a", 1, 4), ("b", 5, 9), ("copy-start", 2, 3), ("c", 8, 12)]
    got = {}
    for n, s, e in scopes.own(evs):
        got[n] = got.get(n, 0) + e - s
    # the while keeps its time outside its body, 0-1 and 4-5; a short event
    # inside `a` takes only its own instant; `c`, started last, holds 8-12 over
    # `b` and the while
    assert got == {"while": 2, "a": 2, "copy-start": 1, "b": 3, "c": 4}
    assert sum(got.values()) == 12


def test_programs_sharing_an_op_name_stay_apart_and_nested_ops_count_once():
    host = [("wave", 0, 100 * MS)]
    step, prog = decode_step(50)
    modules = [("jit_prefill(9)", 0, 20 * MS), prog]
    ops = [("%while.1 = (s32[]) while(...)", 0, 10 * MS),
           ("%fusion.7 = bf16[8] fusion(...)", 1 * MS, 4 * MS),
           ("%fusion.8 = bf16[8] fusion(...)", 5 * MS, 9 * MS),
           ("%fusion.4 = bf16[8] fusion(...)", 12 * MS, 20 * MS),
           ("%fusion.4 = bf16[8] fusion(...)", 51 * MS, 53 * MS),
           ("%fusion.5 = bf16[8] fusion(...)", 53 * MS, 54 * MS),
           ("%copy.9 = bf16[8] copy(...)", 54 * MS, 55 * MS)]
    got = scopes.reduce_events(host + step, [("/device:TPU:0", modules, ops)], SMAP)
    pre, dec = got["programs"]["jit_prefill"], got["programs"]["jit_decode"]
    # the while holds fusion.7 and .8, and keeps only its own 3 ms
    assert pre["scopes"] == {"attn/core": pytest.approx(0.010), "mlp": pytest.approx(0.008)}
    assert pre["busy_s"] == pytest.approx(0.018) and pre["runs"] == 1
    assert dec["scopes"] == {"attn/kv_write": pytest.approx(0.002),
                             "attn/core": pytest.approx(0.001),
                             scopes.UNSCOPED: pytest.approx(0.001)}
    assert dec["unmatched_s"] == pytest.approx(0.001) and dec["runs"] == 1
    assert got["spans"] == {"engine.decode": 1, "engine.dispatch": 1, "engine.fetch": 1}


def test_idle_gaps_are_labelled_by_the_innermost_engine_span():
    host = [("wave", 0, 20 * MS), ("engine.sample", 1 * MS, 2 * MS)]
    # decode spans 2-12 ms, its program 3-11 ms: the clock bounds are -1 and 1 ms
    step, prog = decode_step(2, run=(1, 9), fetch_end=10)
    ops = [("%fusion.5", 0, 1 * MS), ("%fusion.5", prog[1], prog[2]),
           ("%fusion.4", 12 * MS, 16 * MS)]
    got = scopes.reduce_events(host + step, [("/device:TPU:0", [prog], ops)], SMAP)
    assert got["clock_offset_ms"] == 0.0 and got["clock_bounds_ms"] == [-1.0, 1.0]
    # 1-3 ms: its middle, 2 ms, ends the sample and starts the dispatch; the
    # innermost (shortest) covering span is the dispatch. 11-12 ms under the
    # fetch; 16-20 ms outside every engine span
    assert got["idle"] == {"engine.dispatch": pytest.approx(0.002),
                           "engine.fetch": pytest.approx(0.001),
                           scopes.OUTSIDE: pytest.approx(0.004)}
    assert sum(got["idle"].values()) + 0.013 == pytest.approx(got["window_s"])


@pytest.mark.parametrize("offset", [1.5, -0.7])
def test_a_known_clock_offset_is_recovered(offset):
    host, modules, ops = [("wave", 0, 200 * MS)], [], []
    # each step's program starts 0.1-0.9 ms after its dispatch and ends 0.05-0.45
    # ms before its fetch ends, as the host-to-device delays vary
    for i, (lead, lag) in enumerate([(0.9, 0.05), (0.1, 0.3), (0.5, 0.45), (0.3, 0.2)]):
        step, prog = decode_step(10 + 20 * i, offset, run=(lead, 6 - lag), fetch_end=6)
        host += step
        modules.append(prog)
        ops.append(("%fusion.5", prog[1], prog[2]))
    got = scopes.reduce_events(host, [("/device:TPU:0", modules, ops)], SMAP)
    low, high = got["clock_bounds_ms"]
    assert low == pytest.approx(offset - 0.05) and high == pytest.approx(offset + 0.1)
    assert abs(got["clock_offset_ms"] - offset) < 0.1
    busy = 4 * 6 - (0.9 + 0.05 + 0.1 + 0.3 + 0.5 + 0.45 + 0.3 + 0.2)
    assert sum(got["idle"].values()) == pytest.approx(0.2 - busy / 1e3)


def test_crossing_bounds_leave_the_offset_at_zero(capsys):
    host, modules, ops = [("wave", 0, 100 * MS)], [], []
    # the program lasts longer than its dispatch-to-fetch span: no offset fits
    for i in range(2):
        step, prog = decode_step(10 + 30 * i, run=(-1, 8), fetch_end=6)
        host += step
        modules.append(prog)
        ops.append(("%fusion.5", prog[1], prog[2]))
    got = scopes.reduce_events(host, [("/device:TPU:0", modules, ops)], SMAP)
    assert got["clock_offset_ms"] == 0.0
    low, high = got["clock_bounds_ms"]
    assert low > high
    assert "cross" in capsys.readouterr().err


def test_no_window_or_no_device_reads_nothing():
    step, prog = decode_step(0)
    assert scopes.reduce_events(step, [("/device:TPU:0", [prog], [("%f", 0, MS)])], SMAP) is None
    assert scopes.reduce_events([("wave", 0, MS)], [], SMAP) is None


def matmuls(hlo):
    """{program: names of its matrix products}: `convolution` and `dot`
    instructions, and fusions of computations that hold one."""
    out = {}
    for module in re.split(r"(?m)^(?=HloModule\s)", hlo):
        name = re.match(r"HloModule\s+([^\s,]+)", module)
        if not name:
            continue
        ops, calls, comp = {}, {}, None
        for line in module.splitlines():
            head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$", line)
            if head:
                comp = head.group(1)
                continue
            ins = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s([a-z][a-z0-9\-]*)\(", line)
            if ins and comp:
                ops.setdefault(comp, []).append((ins.group(1), ins.group(2)))
                calls[ins.group(1)] = re.findall(r"calls=%?([\w.\-]+)", line)

        def holds(c):
            return any(op in ("convolution", "dot") or any(holds(k) for k in calls[n])
                       for n, op in ops.get(c, []))

        out[name.group(1)] = {"%" + n for c in ops for n, op in ops[c]
                              if op in ("convolution", "dot")
                              or (op == "fusion" and any(holds(k) for k in calls[n]))}
    return out


def test_engine_trace_recorded_on_the_chip(tmp_path):
    """`data/tpu_v5e_engine.*`, recorded by `record_engine_trace.py` on one TPU
    v5e: one `wave` of a 2-layer starcoder2-3b engine at reduced widths, one
    prefill and two decode steps, with the HLO text of both programs."""
    from jax.profiler import ProfileData

    import devtrace

    hlo = gzip.open(DATA / "tpu_v5e_engine.hlo.txt.gz", "rt").read()
    path = tmp_path / "engine.xplane.pb"
    path.write_bytes(gzip.open(DATA / "tpu_v5e_engine.xplane.pb.gz").read())
    smap = scopes.scope_map([hlo])
    got = scopes.reduce_xplane(str(path), smap)

    assert got["spans"] == {"engine.admit": 1, "engine.prefill": 1, "engine.sample": 3,
                            "engine.decode": 2, "engine.dispatch": 2, "engine.fetch": 2}
    low, high = got["clock_bounds_ms"]
    assert low <= got["clock_offset_ms"] <= high
    assert got["clock_offset_ms"] == pytest.approx(-1.2403045)
    progs = got["programs"]
    assert progs["jit_prefill"]["runs"] == 1 and progs["jit_decode"]["runs"] == 2
    for name in ("jit_prefill", "jit_decode"):
        prog = progs[name]
        # every operation the device ran is an instruction of the compiled text
        assert prog["unmatched_s"] == 0.0
        # the scopes hold all the program's time on the device
        assert sum(prog["scopes"].values()) == pytest.approx(prog["busy_s"], rel=1e-9)
        assert {"embed", "norm", "attn/qkv", "attn/core", "attn/out", "mlp", "head",
                scopes.UNSCOPED} <= set(prog["scopes"])
    assert "attn/kv_write" in progs["jit_decode"]["scopes"]
    # the idle time of the window, by span, as the older reduction counts it
    whole = devtrace.reduce_xplane(str(path))
    assert sum(got["idle"].values()) == pytest.approx(whole["window_s"] - whole["busy_s"],
                                                      rel=0.01)
    assert set(got["idle"]) <= {"engine.prefill", "engine.sample", "engine.dispatch",
                                "engine.fetch", "engine.admit", "engine.decode", scopes.OUTSIDE}

    # no matrix product that the device ran is left outside the named scopes
    products = matmuls(hlo)
    device = next(p for p in ProfileData.from_file(str(path)).planes
                  if p.name == "/device:TPU:0")
    lines = {ln.name: list(devtrace._events(ln)) for ln in device.lines}
    ran = set()
    for module, s, e in lines[scopes.MODULES_LINE]:
        program = scopes.module_name(module)
        ran |= {(program, devtrace.short(n)) for n, os_, _ in lines[devtrace.OPS_LINE]
                if s <= os_ < e}
    ran_products = {(p, n) for p, n in ran if n in products.get(p, ())}
    assert {p for p, _ in ran_products} == {"jit_prefill", "jit_decode"}
    assert all(smap[p][n] != scopes.UNSCOPED for p, n in ran_products), ran_products
