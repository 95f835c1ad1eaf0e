"""Share of the traced window, in percent, in which no operation ran on a
chip: the highest over the chips.  Transfers from the host are not
operations there."""

from bench import trace as tr


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    w = run.trace.window
    return max(100.0 * (1.0 - tr.busy_seconds(c, w) / run.trace.window_s)
               for c in run.trace.chips)
