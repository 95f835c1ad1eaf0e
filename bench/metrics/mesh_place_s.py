"""Seconds per call in ``ooc.mesh.place``: ``MeshOocRuntime`` putting the
A, B and C shards onto their chips, until every shard has landed."""

from bench import spans


def read(run):
    return spans.seconds(run, "ooc.mesh.place")
