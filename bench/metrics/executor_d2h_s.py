"""Seconds per call in ``ooc.exec.d2h``: the executor's host time waiting
for a written-back block's product and its copy to the host."""

from bench import spans


def read(run):
    return spans.seconds(run, "ooc.exec.d2h")
