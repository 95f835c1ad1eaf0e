"""Mean over the calls of the call's wall time less the executor's own
(``ScheduleExecutor.last_wall_seconds``): the entry layer's host work, such
as the copy of C into the result, partitioning and the schedule build."""


def read(run):
    d = [c.wall_s - c.counters["executor_wall_s"] for c in run.calls
         if "executor_wall_s" in c.counters]
    return sum(d) / len(d) if d else None
