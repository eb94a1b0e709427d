"""Device milliseconds in `attn/core` (scores, mask, softmax and the product
with V, over the KV cache) per run of the decode program `jit_decode`, over
the traced waves (`scopes.py`)."""

import scopes


def read(run):
    return scopes.program_ms(run, "jit_decode", "attn/core")
