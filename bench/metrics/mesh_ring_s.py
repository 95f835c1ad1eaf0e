"""Seconds per call in ``ooc.mesh.ring``: the SUMMA program dispatched and
run to completion on the mesh."""

from bench import spans


def read(run):
    return spans.seconds(run, "ooc.mesh.ring")
