"""Mean milliseconds of an untraced wave's prefill, with the cache splice and
the logits' copy to the host, as `ServeEngine.prefill_s` counts them."""


def read(run):
    waves = [w for w in run.get("waves") or [] if not w["traced"]]
    if not waves:
        return None
    return 1e3 * sum(w["prefill_s"] for w in waves) / len(waves)
