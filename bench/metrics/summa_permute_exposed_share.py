"""Of the time in which the SUMMA program's operations run on a chip, the
share in percent in which a collective-permute runs and no other operation
does: the highest over the chips.  The programs that permute are the ones
counted."""

from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    shares = []
    for chip in run.trace.chips:
        exposed, total = tr.exposed_permute(chip, run.trace.window)
        if total > 0:
            shares.append(100.0 * exposed / total)
    return max(shares) if shares else None
