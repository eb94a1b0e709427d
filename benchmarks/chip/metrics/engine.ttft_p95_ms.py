"""95th percentile over the untraced waves' requests of the milliseconds from
its wave's `serve()` call to its first token (`serving.request_ms`)."""

import numpy as np

import serving


def read(run):
    waves = [w for w in run.get("waves") or [] if not w["traced"]]
    if not waves:
        return None
    return float(np.percentile(serving.request_ms(waves)[0], 95))
