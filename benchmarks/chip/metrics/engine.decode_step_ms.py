"""Milliseconds per decode step over the window's untraced waves:
`ServeEngine.decode_s` summed over waves, over `decode_steps` summed. Each
step includes the logits' copy to the host."""


def read(run):
    waves = [w for w in run.get("waves") or [] if not w["traced"]]
    steps = sum(w["decode_steps"] for w in waves)
    if not steps:
        return None
    return 1e3 * sum(w["decode_s"] for w in waves) / steps
