"""Seconds of XLA backend compilation during set-up, from JAX's compile events."""


def read(run):
    return run["compile_s"]
