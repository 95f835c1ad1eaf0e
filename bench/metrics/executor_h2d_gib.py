"""Mean over the calls of the executor's H2D bytes
(``ScheduleExecutor.last_h2d_bytes``), in GiB per call."""


def read(run):
    b = [c.counters["h2d_bytes"] for c in run.calls
         if "h2d_bytes" in c.counters]
    return sum(b) / len(b) / 2**30 if b else None
