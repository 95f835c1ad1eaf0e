"""Useful work of the calls in the window, 2·M·N·K each, over the time from
the window's start to the return of the last call, in TFLOP/s."""

from bench.work import gemm_flops


def read(run):
    if not run.calls:
        return None
    return sum(gemm_flops(c.m, c.n, c.k) for c in run.calls) \
        / run.window_s / 1e12
