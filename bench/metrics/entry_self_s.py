"""Seconds per call in ``ooc.gemm`` outside ``ooc.entry.copy_c`` and
``ooc.exec.run``: the entry's other host work, such as argument handling,
the partition and the schedule build."""

from bench import spans


def read(run):
    return spans.self_seconds(run, "ooc.gemm", "ooc.entry.copy_c",
                              "ooc.exec.run")
