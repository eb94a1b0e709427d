"""Share of the untraced waves' seconds that the chip's roofline needs: each
prefill and decode program bound by its needed operations at the bf16 peak or
its needed bytes (weights once, valid keys and values read, new ones written)
at the HBM bandwidth, whichever takes longer, summed over the waves."""

import flops


def read(run):
    waves, peak = [w for w in run.get("waves") or [] if not w["traced"]], run.get("peak")
    if not waves or not peak:
        return None
    cfg, S, B = run["config"], run["prompt_len"], run["slots"]
    least = sum(flops.wave_roofline_s(cfg, B, S, w["decode_steps"], peak) for w in waves)
    secs = sum(w["t1"] - w["t0"] for w in waves)
    return 100.0 * least / (secs * run["chips"])
