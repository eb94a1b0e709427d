"""Milliseconds per decode step in which the device idles while the host
fetches the step's logits (`engine.fetch`), over the traced waves, the idle
stretches labelled on the host's clock (`scopes.py`)."""

import scopes


def read(run):
    return scopes.idle_ms(run, ("engine.fetch",))
