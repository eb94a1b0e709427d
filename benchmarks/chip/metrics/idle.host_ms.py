"""Milliseconds per decode step in which the device idles while the host
samples (`engine.sample`: argmax and bookkeeping) or dispatches the next step
(`engine.dispatch`), over the traced waves (`scopes.py`)."""

import scopes


def read(run):
    return scopes.idle_ms(run, ("engine.sample", "engine.dispatch"))
