"""The block product's share of its roofline, in percent.

Least time: the larger of the calls' 2·M·N·K at the peak rate of the
precision the configuration states (its ``peak_rate`` in the peak table)
and their bytes for one read of A, B and C and one write of the result at
HBM bandwidth.  Kernel time: the summed device time of the
``jit__block_dgemm`` program in the trace.  Which term bounds it goes
into the run's notes."""

from bench import trace as tr
from bench import work

PROGRAM = "jit__block_dgemm"


def read(run):
    if run.trace is None or not run.calls or not run.peaks:
        return None
    kernel_s = sum(tr.module_seconds(chip, PROGRAM, run.trace.window)
                   for chip in run.trace.chips)
    if kernel_s <= 0:
        return None
    flops = sum(work.gemm_flops(c.m, c.n, c.k) for c in run.calls)
    nbytes = sum(work.gemm_min_bytes(c.m, c.n, c.k, c.bytes_per_el)
                 for c in run.calls)
    least, bound = work.least_seconds(
        flops, nbytes, run.peaks["flop_per_s"][run.config["peak_rate"]],
        run.peaks["hbm_bytes_per_s"])
    run.notes["block_dgemm_roofline"] = f"{bound}-bound"
    return work.share(least, kernel_s)
