"""Seconds from the process's start to the window's: JAX start-up, the
operands, compilation or loading from the cache, and the warm-up call."""


def read(run):
    return run.setup_s
