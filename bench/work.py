"""Work, bytes and peaks: the yardstick of every roofline share.

Work and bytes come from the problem's shape, never from how the program
blocks it, so a share stays comparable whatever implements the kernel.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
MAX_SHARE = 105.0   # percent; above it the work or the bytes are overcounted


def gemm_flops(m: int, n: int, k: int) -> int:
    """Operations of ``alpha * A @ B + beta * C``: 2·M·N·K."""
    return 2 * m * n * k


def gemm_min_bytes(m: int, n: int, k: int, bytes_per_el: int) -> int:
    """HBM bytes of one read of A, B and C and one write of the result."""
    return bytes_per_el * (m * k + k * n + 2 * m * n)


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; an unknown device raises."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, flop_per_s: float,
                  bytes_per_s: float) -> tuple[float, str]:
    """The least time the chip could take, and which term bounds it."""
    compute, memory = flops / flop_per_s, nbytes / bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")


def share(least_s: float, measured_s: float) -> float:
    """``least_s / measured_s`` in percent.  Over :data:`MAX_SHARE` is an
    error: the least time or the measured time leaves out part of the
    work."""
    pct = 100.0 * least_s / measured_s
    if pct > MAX_SHARE:
        raise ValueError(f"share of {pct:.1f}% is over {MAX_SHARE}%: "
                         f"work or bytes overcounted, or time undercounted")
    return pct
