"""Share of the chips' bf16 peak that the untraced waves' needed operations
fill: prefill and decode operations (causal attention as its pairs, the head
where its logits are used) over the waves' seconds, the chips and the peak."""

import flops


def read(run):
    waves, peak = [w for w in run.get("waves") or [] if not w["traced"]], run.get("peak")
    if not waves or not peak:
        return None
    cfg, S, B = run["config"], run["prompt_len"], run["slots"]
    ops = sum(flops.wave_flops(cfg, B, S, w["decode_steps"]) for w in waves)
    secs = sum(w["t1"] - w["t0"] for w in waves)
    return 100.0 * ops / (secs * run["chips"] * peak["bf16_flops"])
