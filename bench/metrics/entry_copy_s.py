"""Seconds per call in ``ooc.entry.copy_c``: ``HostOocRuntime``'s copy of C
into the result, before the executor runs."""

from bench import spans


def read(run):
    return spans.seconds(run, "ooc.entry.copy_c")
