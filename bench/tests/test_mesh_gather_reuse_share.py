"""``mesh_gather_reuse_share``: on spans made by hand and on a traced run
of the tiny mesh cell on four CPU devices."""

import pytest

from bench import harness
from bench import trace as tr

from test_mesh_cell import roomy_root, with_tiny_mesh_metrics  # noqa: F401

read = harness.load_reader("mesh_gather_reuse_share")


def run_of(host, window):
    """A run whose trace has the host events ``host``, ``(name, start,
    end)`` in ns, and no chip."""
    t = tr.Trace([], [tr.Event("bench.window", *window)]
                 + [tr.Event(*e) for e in host], window)
    return harness.Run({}, 0.0, 0.0, [], 0, {}, t)


def call(start, got):
    """One mesh call's spans: placement, ring and gather, and within the
    gather getting the result (``"reuse"``, ``"alloc"``, or ``None`` for a
    program that says not)."""
    spans = [("ooc.gemm", start, start + 100),
             ("ooc.mesh.place", start + 1, start + 20),
             ("ooc.mesh.ring", start + 20, start + 30),
             ("ooc.mesh.gather", start + 30, start + 95)]
    if got:
        spans.append((f"ooc.mesh.{got}_result", start + 31, start + 32))
    return spans


def test_share_of_the_gathers_that_reused_the_result():
    host = call(0, "alloc") + call(100, "reuse") + call(200, "reuse") \
        + call(300, "reuse")
    assert read(run_of(host, (0, 400))) == pytest.approx(75.0)
    # a span's metadata in its name does not hide it
    host = [(n + "#bytes=64#" if n == "ooc.mesh.gather" else n, s, e)
            for n, s, e in call(0, "reuse")] + call(100, "alloc")
    assert read(run_of(host, (0, 200))) == pytest.approx(50.0)
    # the host tier's result spans are not the gather's
    host = call(0, None) + [("ooc.entry.reuse_result", 31, 32)]
    assert read(run_of(host, (0, 100))) is None


def test_gathers_clipped_at_the_window_count_and_those_outside_do_not():
    host = call(-50, "alloc") + call(100, "reuse") + call(170, "reuse") \
        + call(300, "alloc") + call(-200, "alloc")
    # in the window [0, 220): the gathers at -20..45 (its marker before
    # the window), 130..195 and 200..265 (its marker after the window's
    # end)
    assert read(run_of(host, (0, 220))) == pytest.approx(200.0 / 3)


def test_nothing_to_read_without_the_spans():
    # no gather in the window: device operands, or no call at all
    assert read(run_of([("ooc.gemm", 0, 80)], (0, 100))) is None
    assert read(run_of(call(200, "reuse"), (0, 100))) is None
    # gathers that do not say how they got the result, as a program
    # without the recycling writes them
    assert read(run_of(call(0, None) + call(100, None), (0, 200))) is None
    assert read(harness.Run({}, 0.0, 0.0, [], 0, {}, None)) is None


def test_traced_cpu_mesh_run_reuses_from_its_first_call(roomy_root):
    spec = harness.load_spec(roomy_root)
    (entry,) = [m for m in spec["per_layer"]
                if m["name"] == "mesh_gather_reuse_share"]
    assert entry["workloads"] == ["summa_f32.mesh4_n40960"]
    root = with_tiny_mesh_metrics(roomy_root)
    out = harness.run_workload("summa_f32.tiny_mesh", 2**31 + 17, 0.3, True,
                               root=root, require_tpu=False)
    # the warm-up is one whole call of the window's shape on the same
    # runtime, so every timed call writes into the result before it
    assert out["correct"] and out["attempted"] > 1
    assert out["metrics"]["mesh_gather_reuse_share"]["value"] \
        == pytest.approx(100.0)
