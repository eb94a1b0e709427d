"""Host milliseconds per decode step of `ServeEngine`'s own work over the
untraced waves: the argmax and bookkeeping (`sample_s`) and the step's
dispatch (`dispatch_s`), summed over waves, over `decode_steps` summed."""


def read(run):
    waves = [w for w in run.get("waves") or [] if not w["traced"]]
    steps = sum(w["decode_steps"] for w in waves)
    if not steps or any(w.get("sample_s") is None or w.get("dispatch_s") is None for w in waves):
        return None
    return 1e3 * sum(w["sample_s"] + w["dispatch_s"] for w in waves) / steps
