"""Reduction of a JAX profiler trace to the numbers the metrics read.

A trace holds one plane per TPU (``/device:TPU:<i>``) whose ``XLA Ops``
line has every operation the chip ran and whose ``XLA Modules`` line has
one event per program run, named ``<jit name>(<fingerprint>)``.  The
``/host:CPU`` plane has one line per host thread; the benchmark's own
``TraceAnnotation`` spans (``bench.*``) are there too.  Times are in
nanoseconds from the start of the trace, on one clock for host and device.
Transfers between host and device are not operations on the device plane.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.window"   # the host annotation around the measured window
PERMUTE = "collective-permute"
# control flow: such an op spans the ops of its body on the same line
CONTAINERS = {"while", "conditional", "call"}


@dataclass(frozen=True)
class Event:
    name: str
    start: int
    end: int


@dataclass
class Chip:
    ops: list = field(default_factory=list)       # [Event], XLA Ops line
    modules: list = field(default_factory=list)   # [Event], XLA Modules line


@dataclass
class Trace:
    chips: list          # [Chip], in device order
    host: list           # [Event], of every host thread
    window: tuple        # (start, end) ns of the measured window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def _events(line) -> list:
    return [Event(e.name, int(e.start_ns), int(e.end_ns))
            for e in line.events]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the log directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    chips, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = Chip()
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip.ops = _events(line)
                elif line.name == "XLA Modules":
                    chip.modules = _events(line)
            chips[int(plane.name.rsplit(":", 1)[1])] = chip
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(_events(line))
    marks = [e for e in host if e.name == WINDOW]
    if not marks:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    host.sort(key=lambda e: e.start)
    return Trace([chips[i] for i in sorted(chips)], host,
                 (marks[0].start, marks[0].end))


# -- interval arithmetic ----------------------------------------------------
def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` that ``b`` leaves
    uncovered (``b`` disjoint and sorted too)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- what the metrics read --------------------------------------------------
def busy(chip: Chip, window) -> list:
    """Intervals of the window in which an operation ran on the chip."""
    return clip(union((e.start, e.end) for e in chip.ops), *window)


def busy_seconds(chip: Chip, window) -> float:
    return length(busy(chip, window)) / 1e9


def idle_gaps(chip: Chip, window) -> list:
    return subtract([tuple(window)], busy(chip, window))


def module_name(event_name: str) -> str:
    """``jit__block_dgemm(1189...)`` -> ``jit__block_dgemm``."""
    return event_name.split("(", 1)[0]


def module_seconds(chip: Chip, name: str, window) -> float:
    """Device time of the runs of the program ``name`` in the window."""
    return length(clip(((e.start, e.end) for e in chip.modules
                        if module_name(e.name) == name), *window)) / 1e9


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_container(event_name: str) -> bool:
    """``%while.17 = ...``: the op is its body's ops, not work of its own."""
    return op_name(event_name).split(".", 1)[0] in CONTAINERS


def _containing(events, t: int):
    """The event of the sorted, disjoint ``events`` that contains ``t``."""
    i = bisect.bisect_right([e.start for e in events], t) - 1
    return events[i] if i >= 0 and events[i].end >= t else None


def device_ops(trace: Trace, top: int = 10) -> list:
    """``[name, seconds]`` of the operations that took most device time,
    named ``<program>/<op>`` and averaged over the chips; control flow
    ops are left out, their bodies' ops are counted."""
    tot = {}
    for chip in trace.chips:
        mods = sorted(chip.modules, key=lambda e: e.start)
        for e in chip.ops:
            s, t = max(e.start, trace.window[0]), min(e.end, trace.window[1])
            if t <= s or is_container(e.name):
                continue
            mod = _containing(mods, e.start)
            key = f"{module_name(mod.name) if mod else '?'}/{op_name(e.name)}"
            tot[key] = tot.get(key, 0) + (t - s)
    n = max(len(trace.chips), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / n / 1e9] for k, v in ranked]


def host_activity(trace: Trace, t: int) -> str:
    """What the host was doing at ``t``: the shortest host event that
    covers it, within the benchmark annotation that covers it."""
    cover = [e for e in trace.host if e.start <= t <= e.end]
    ours = [e for e in cover if e.name.startswith("bench.")
            and e.name != WINDOW]
    other = [e for e in cover if not e.name.startswith("bench.")]
    parts = [min(x, key=lambda e: e.end - e.start).name
             for x in (ours, other) if x]
    return " > ".join(parts) or "no host event"


def longest_gaps(trace: Trace, top: int = 10) -> list:
    """``[what the host was doing, seconds]`` of the longest idle gaps of
    chip 0 in the window."""
    gaps = sorted(idle_gaps(trace.chips[0], trace.window),
                  key=lambda g: g[0] - g[1])[:top]
    return [[host_activity(trace, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps]


def exposed_permute(chip: Chip, window) -> tuple[float, float]:
    """``(seconds in which a collective-permute ran and no other
    operation did, seconds in which any operation of the programs that
    permute ran)``.  A control flow op counts as neither: it spans the
    ops of its body."""
    mods = sorted(chip.modules, key=lambda e: e.start)
    ops = [(e, _containing(mods, e.start)) for e in chip.ops
           if not is_container(e.name)]
    progs = {m for e, m in ops if m is not None and PERMUTE in e.name}
    perm = [(e.start, e.end) for e, m in ops if PERMUTE in op_name(e.name)]
    other = [(e.start, e.end) for e, m in ops
             if PERMUTE not in op_name(e.name)]
    in_progs = [(e.start, e.end) for e, m in ops if m in progs]
    exposed = subtract(clip(union(perm), *window),
                       clip(union(other), *window))
    return (length(exposed) / 1e9,
            length(clip(union(in_progs), *window)) / 1e9)
