"""95th percentile over the untraced waves' requests of the milliseconds per
token after the first, the host loop included (`serving.request_ms`)."""

import numpy as np

import serving


def read(run):
    waves = [w for w in run.get("waves") or [] if not w["traced"]]
    if not waves:
        return None
    return float(np.percentile(serving.request_ms(waves)[1], 95))
