"""The program's own spans in a traced run, per call.

The engine writes ``ooc.*`` spans onto the profiler's host plane
(``repro.obs.annotate``): ``ooc.gemm`` around an ``ooc_gemm`` call,
``ooc.entry.copy_c`` around its copy of C into the result,
``ooc.exec.run`` around the executor's run, and ``ooc.exec.h2d``,
``ooc.exec.d2h`` and ``ooc.exec.store`` around each transfer.  A program
that writes none leaves the readers nothing to read.
"""

from __future__ import annotations

from bench import trace as tr


def seconds(run, name: str) -> float | None:
    """Seconds of the window covered by the spans named ``name`` (before
    any ``#``-encoded metadata), counted once where spans of several
    threads overlap, per call; ``None`` where the window has none."""
    if run.trace is None or not run.calls:
        return None
    covered = tr.clip(tr.union((e.start, e.end) for e in run.trace.host
                               if e.name.split("#", 1)[0] == name),
                      *run.trace.window)
    if not covered:
        return None
    return tr.length(covered) / 1e9 / len(run.calls)


def self_seconds(run, whole: str, *parts: str) -> float | None:
    """``whole``'s seconds per call less those of its ``parts`` (a part
    with no span counts 0), so that a span and its parts tile it."""
    total = seconds(run, whole)
    if total is None:
        return None
    return total - sum(seconds(run, p) or 0.0 for p in parts)
