"""Seconds per call in ``ooc.exec.run`` outside its transfer spans
(``ooc.exec.h2d``, ``.d2h`` and ``.store``): the plan compile, compute
dispatch and the loop.  In the executor's concurrent mode the transfer
spans of different engine threads overlap, and this reads low."""

from bench import spans


def read(run):
    return spans.self_seconds(run, "ooc.exec.run", "ooc.exec.h2d",
                              "ooc.exec.d2h", "ooc.exec.store")
