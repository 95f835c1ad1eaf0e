"""Seconds per call in ``ooc.mesh.gather``: the sharded result copied into
host memory."""

from bench import spans


def read(run):
    return spans.seconds(run, "ooc.mesh.gather")
