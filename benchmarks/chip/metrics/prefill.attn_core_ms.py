"""Device milliseconds in `attn/core` (causal scores, softmax and the product
with V) per run of the prefill program `jit_prefill`, over the traced waves
(`scopes.py`)."""

import scopes


def read(run):
    return scopes.program_ms(run, "jit_prefill", "attn/core")
