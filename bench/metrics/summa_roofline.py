"""The SUMMA program's share of its roofline on one chip, in percent: the
lowest over the chips.

A chip's work in a call is its own row block of the product: 2·(M/P)·N·K
at the peak rate of the precision the configuration states, and the bytes
of one read of its A and C shards and of all of B (which the ring brings
past it) and one write of its C shard at HBM bandwidth.  The least time is
the larger of the two, summed over the calls; the measured time is the
chip's device time of the ``jit_summa_ring`` program in the window.  Which
term bounds it goes into the run's notes."""

from bench import trace as tr
from bench import work

PROGRAM = "jit_summa_ring"


def read(run):
    if run.trace is None or not run.calls or not run.peaks:
        return None
    chips = [tr.module_seconds(c, PROGRAM, run.trace.window)
             for c in run.trace.chips]
    if not chips or min(chips) <= 0:
        return None
    p = run.config["chips"]
    flops = sum(work.gemm_flops(c.m // p, c.n, c.k) for c in run.calls)
    nbytes = sum(work.gemm_min_bytes(c.m // p, c.n, c.k, c.bytes_per_el)
                 for c in run.calls)
    least, bound = work.least_seconds(
        flops, nbytes, run.peaks["flop_per_s"][run.config["peak_rate"]],
        run.peaks["hbm_bytes_per_s"])
    run.notes["summa_roofline"] = f"{bound}-bound"
    return min(work.share(least, s) for s in chips)
