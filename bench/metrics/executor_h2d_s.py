"""Seconds per call in ``ooc.exec.h2d``: the executor's host time slicing
blocks and putting them on the chip, each landed before the next.  With
``executor_h2d_gib`` it gives the achieved H2D rate."""

from bench import spans


def read(run):
    return spans.seconds(run, "ooc.exec.h2d")
