"""``entry_result_reuse_share``: on spans made by hand and on a traced run
of the program on the CPU."""

import pytest

from bench import harness
from bench import trace as tr

read = harness.load_reader("entry_result_reuse_share")


def run_of(host, window):
    """A run whose trace has the host events ``host``, ``(name, start,
    end)`` in ns, and no chip."""
    t = tr.Trace([], [tr.Event("bench.window", *window)]
                 + [tr.Event(*e) for e in host], window)
    return harness.Run({}, 0.0, 0.0, [], 0, {}, t)


def call(start, got):
    """One call's entry spans: the copy, and within it getting the result
    (``"reuse"``, ``"alloc"``, or ``None`` for a program that says not)."""
    spans = [("ooc.gemm", start, start + 100),
             ("ooc.entry.copy_c", start + 5, start + 35)]
    if got:
        spans.append((f"ooc.entry.{got}_result", start + 6, start + 7))
    return spans


def test_share_of_the_copies_that_reused_the_result():
    host = call(0, "alloc") + call(100, "reuse") + call(200, "reuse") \
        + call(300, "reuse")
    assert read(run_of(host, (0, 400))) == pytest.approx(75.0)
    # a span's metadata in its name does not hide it
    host = [(n + "#reused=1#" if n == "ooc.entry.copy_c" else n, s, e)
            for n, s, e in call(0, "reuse")] + call(100, "alloc")
    assert read(run_of(host, (0, 200))) == pytest.approx(50.0)


def test_copies_clipped_at_the_window_count_and_those_outside_do_not():
    host = call(-20, "alloc") + call(100, "reuse") + call(190, "reuse") \
        + call(300, "alloc") + call(-200, "alloc")
    # in the window [0, 200): the copies at -15..15 (its marker before the
    # window), 105..135 and 195..225 (its marker after the window's end)
    assert read(run_of(host, (0, 200))) == pytest.approx(200.0 / 3)


def test_nothing_to_read_without_the_spans():
    # no copy in the window: an in-core call, or none at all
    assert read(run_of([("ooc.gemm", 0, 80)], (0, 100))) is None
    assert read(run_of(call(200, "reuse"), (0, 100))) is None
    # copies that do not say how they got the result, as a program
    # without the recycling writes them
    assert read(run_of(call(0, None) + call(100, None), (0, 200))) is None
    assert read(harness.Run({}, 0.0, 0.0, [], 0, {}, None)) is None


def test_traced_cpu_run_reuses_after_its_first_call(tiny_root):
    out = harness.run_workload("mmooc_f32.tiny_ooc", 2**31 + 17, 0.3, True,
                               root=tiny_root, require_tpu=False)
    # the warm-up's calls are smaller: the window's first call allocates
    calls = out["attempted"]
    assert calls > 1
    assert out["metrics"]["entry_result_reuse_share"]["value"] \
        == pytest.approx(100.0 * (calls - 1) / calls)
