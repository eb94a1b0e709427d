"""Share of the traced waves' span in which no operation ran on the device,
averaged over the chips (`devtrace.py`)."""


def read(run):
    tr = run.get("trace")
    if not tr or not run.get("waves") or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
