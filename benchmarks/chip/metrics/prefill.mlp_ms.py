"""Device milliseconds in `mlp` per run of the prefill program `jit_prefill`,
over the traced waves (`scopes.py`)."""

import scopes


def read(run):
    return scopes.program_ms(run, "jit_prefill", "mlp")
