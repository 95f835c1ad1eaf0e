"""Operands of the GEMM cells, made on the host from the run's seed.

``random_matrix`` is a copy of ``chip_smoke.random_matrix`` with a scale
and a worker count added: the benchmark keeps its own generator so that a
change to the program cannot change the inputs it is measured on.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1024            # rows per child seed
MUTATE_STREAM = 7       # SeedSequence tags of the per-call draws
CHECK_STREAM = 11


def random_matrix(shape, seed: int, stream: int, *, scale: float = 1.0,
                  workers: int | None = None, out=None) -> np.ndarray:
    """Normal float32 values of standard deviation ``scale`` from
    ``(seed, stream)``, filled in parallel row chunks, each from its own
    child seed, so the values do not depend on the number of workers."""
    out = np.empty(shape, np.float32) if out is None else out
    seqs = np.random.SeedSequence([seed, stream]).spawn(
        -(-shape[0] // CHUNK))

    def fill(i):
        blk = out[i * CHUNK:(i + 1) * CHUNK]
        np.random.default_rng(seqs[i]).standard_normal(out=blk,
                                                       dtype=np.float32)
        if scale != 1.0:
            blk *= np.float32(scale)

    with ThreadPoolExecutor(workers or os.cpu_count()) as pool:
        list(pool.map(fill, range(len(seqs))))
    return out


def mutate(A: np.ndarray, B: np.ndarray, seed: int, call: int,
           band: int) -> list:
    """Rewrite, in place, one row of A and one row of B in every ``band``
    rows, at positions and with values drawn from ``(seed, call)``.

    Every row block of A and, since a row of B crosses all its columns,
    every column block of B then differ from the previous call's.  Returns
    what :func:`undo` needs to restore the previous operands."""
    rng = np.random.default_rng([seed, MUTATE_STREAM, call])
    saved = []
    for X in (A, B):
        starts = np.arange(0, X.shape[0], band)
        rows = np.minimum(starts + rng.integers(0, band, starts.size),
                          X.shape[0] - 1)
        saved.append((X, rows, X[rows].copy()))
        X[rows] = rng.standard_normal((rows.size, X.shape[1]),
                                      dtype=np.float32).astype(X.dtype)
    return saved


def undo(saved: list) -> None:
    """Put back the rows one :func:`mutate` overwrote."""
    for X, rows, old in reversed(saved):
        X[rows] = old


def check_rows(m: int, seed: int, call: int, groups: int,
               rows: int) -> np.ndarray:
    """Indices of ``groups`` runs of ``rows`` consecutive full-width rows,
    one run at a position drawn from ``(seed, call)`` inside each of
    ``groups`` equal slices of the ``m`` rows."""
    span = m // groups
    if span < rows:
        raise ValueError(f"{groups} groups of {rows} rows exceed {m} rows")
    rng = np.random.default_rng([seed, CHECK_STREAM, call])
    starts = [g * span + int(rng.integers(0, span - rows + 1))
              for g in range(groups)]
    return np.concatenate([np.arange(s, s + rows) for s in starts])
