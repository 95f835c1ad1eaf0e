"""The readers of the program's own ``ooc.*`` spans: on spans made by hand,
on a traced run on the CPU, and on a trace recorded on the chip and kept
in ``data/``."""

from pathlib import Path

import pytest

from bench import harness
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"

READERS = ("entry_copy_s", "entry_self_s", "executor_h2d_s",
           "executor_d2h_s", "executor_store_s", "executor_self_s")


def run_of(host, window, calls):
    """A run of ``calls`` calls whose trace has the host events ``host``,
    ``(name, start, end)`` in ns, and no chip."""
    t = tr.Trace([], [tr.Event("bench.window", *window)]
                 + [tr.Event(*e) for e in host], window)
    return harness.Run({}, 0.0, 0.0, [
        harness.Call(i, 0.0, 1, 1, 1, 4, {}, None, None, None)
        for i in range(calls)], 0, {}, t)


def read_all(run):
    return {m: harness.load_reader(m)(run) for m in READERS}


def ns(x):
    """Seconds per call as the readers give them, for ns per call."""
    return pytest.approx(x * 1e-9)


def test_serial_spans_tile_each_call():
    host = []
    for c in (0, 100):     # two calls, the second one with its metadata
        host += [("bench.call", c, c + 100), ("ooc.gemm", c, c + 100),
                 ("ooc.entry.copy_c", c + 5, c + 35),
                 ("ooc.exec.run", c + 40, c + 95),
                 ("ooc.exec.h2d#bytes=64#", c + 45, c + 55),
                 ("ooc.exec.h2d#bytes=64#", c + 60, c + 70),
                 ("ooc.exec.d2h", c + 72, c + 80),
                 ("ooc.exec.store", c + 80, c + 85),
                 ("TransferToDevice", c + 46, c + 54)]
    got = read_all(run_of(host, (0, 200), 2))
    assert got == {"entry_copy_s": ns(30), "entry_self_s": ns(15),
                   "executor_h2d_s": ns(20), "executor_d2h_s": ns(8),
                   "executor_store_s": ns(5), "executor_self_s": ns(22)}
    assert sum(got.values()) == ns(100)


def test_overlapping_threads_count_once_and_the_window_clips():
    # concurrent mode: two H2D spans overlap on two threads (union 30, not
    # 40); the call's spans start before the window and end after it
    host = [("ooc.gemm", -50, 150), ("ooc.entry.copy_c", -40, 20),
            ("ooc.exec.run", 25, 140),
            ("ooc.exec.h2d", 30, 50), ("ooc.exec.h2d", 40, 60),
            ("ooc.exec.d2h", 55, 70), ("ooc.exec.store", 70, 80),
            ("ooc.exec.h2d", 90, 130)]
    got = read_all(run_of(host, (0, 100), 1))
    assert got["entry_copy_s"] == ns(20)
    assert got["executor_h2d_s"] == ns(40)          # 30-60 and 90-100
    assert got["entry_self_s"] == ns(100 - 20 - 75)
    assert got["executor_self_s"] == ns(75 - 40 - 15 - 10)
    assert sum(got.values()) == ns(100)


def test_a_program_without_spans_reads_nothing():
    host = [("bench.call[0]", 0, 100), ("TransferToDevice", 10, 20)]
    assert set(read_all(run_of(host, (0, 100), 1)).values()) == {None}
    untraced = harness.Run({}, 0.0, 0.0, [], 0, {}, None)
    assert set(read_all(untraced).values()) == {None}
    # an in-core call: the entry has only itself
    got = read_all(run_of([("ooc.gemm", 0, 80)], (0, 100), 1))
    assert got == {**{m: None for m in READERS}, "entry_self_s": ns(80)}


def test_the_cell_reports_the_six_after_the_accepted_metrics(tiny_root):
    spec = harness.load_spec(tiny_root)
    names = [m["name"] for m in harness.metrics_for(
        spec, "mmooc_f32.ooc_n40960", True)]
    assert names == ["entry_host_s", "executor_h2d_gib",
                     "block_dgemm_roofline", "device_idle_share", *READERS]


def test_traced_cpu_run_reports_the_six(tiny_root):
    lines = []
    out = harness.run_workload("mmooc_f32.tiny_ooc", 2**31 + 11, 0.3, True,
                               root=tiny_root, require_tpu=False,
                               log=lines.append)
    # no chip on the CPU: the device trace's metrics read nothing
    assert set(out["metrics"]) == {"entry_host_s", "executor_h2d_gib",
                                   *READERS}
    got = {m: out["metrics"][m]["value"] for m in READERS}
    assert all(v > 0 for v in got.values()), got
    # they tile ooc.gemm, which lies within the harness's call
    line = next(x for x in lines if x.startswith("calls: "))
    walls = [float(w) for w in line.split("walls ")[1].split(";")[0].split()]
    mean_wall = sum(walls) / len(walls)       # printed to the microsecond
    assert 0.9 * mean_wall < sum(got.values()) <= mean_wall + 1e-6
    # the entry measured from outside also holds the plan compile
    assert got["entry_copy_s"] + got["entry_self_s"] \
        < out["metrics"]["entry_host_s"]["value"]


# -- a trace recorded on a TPU v5e with the program's spans: six host-tier
# calls at n = 512 with a budget that streams them in blocks (the tiny_ooc
# cell), whose run printed these values.
RECORDED = {"entry_copy_s": 0.00011547816666666667,
            "entry_self_s": 0.0011321936666666699,
            "executor_h2d_s": 0.022762000333333334,
            "executor_d2h_s": 0.007304561333333333,
            "executor_store_s": 0.000290354,
            "executor_self_s": 0.017930758499999994}


@pytest.fixture(scope="module")
def tiny_spans():
    return tr.load(str(DATA / "tiny_ooc_spans.xplane.pb"))


def test_recorded_longest_gap_lies_in_a_program_span(tiny_spans):
    t = tiny_spans
    s, e = max(tr.idle_gaps(t.chips[0], t.window), key=lambda g: g[1] - g[0])
    mid = (s + e) // 2
    assert any(x.name.startswith("ooc.") and x.start <= mid <= x.end
               for x in t.host)
    name, seconds = tr.longest_gaps(t)[0]
    assert seconds == pytest.approx((e - s) / 1e9)
    assert name.startswith("bench.call[") and " > ooc." in name


def test_readers_reproduce_the_recorded_spans_run(tiny_spans):
    run = harness.Run({}, 0.0, 0.0, [
        harness.Call(i, 0.0, 512, 512, 512, 4, {}, None, None, None)
        for i in range(6)], 0, {}, tiny_spans)
    assert read_all(run) == pytest.approx(RECORDED)
