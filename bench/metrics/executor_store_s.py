"""Seconds per call in ``ooc.exec.store``: the executor's host time storing
landed blocks into their slices of the result."""

from bench import spans


def read(run):
    return spans.seconds(run, "ooc.exec.store")
