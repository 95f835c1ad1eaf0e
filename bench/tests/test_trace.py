"""The trace reduction, on intervals made by hand and on traces recorded
on the chip and kept in ``data/``."""

from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]
    assert tr.length([(0, 3), (5, 8)]) == 6


def chip(ops, modules):
    return tr.Chip([tr.Event(n, s, e) for n, s, e in ops],
                   [tr.Event(n, s, e) for n, s, e in modules])


def test_busy_idle_and_module_time():
    c = chip([("%fusion = f32 fusion()", 10, 30), ("%copy = copy()", 25, 40),
              ("%fusion.1 = f32 fusion()", 60, 70)],
             [("jit__block_dgemm(123)", 10, 40), ("jit_other(9)", 60, 70)])
    w = (0, 100)
    assert tr.busy(c, w) == [(10, 40), (60, 70)]
    assert tr.busy_seconds(c, w) == 40e-9
    assert tr.idle_gaps(c, w) == [(0, 10), (40, 60), (70, 100)]
    assert tr.module_seconds(c, "jit__block_dgemm", w) == 30e-9
    assert tr.module_seconds(c, "jit__block_dgemm", (20, 100)) == 20e-9


def test_exposed_permute_counts_only_time_without_other_ops():
    c = chip([("%collective-permute-start = x", 0, 2), ("%fusion = y", 2, 10),
              ("%collective-permute-done = x", 10, 14),
              ("%fusion.2 = y", 13, 20), ("%other = z", 40, 50)],
             [("jit_ring(1)", 0, 20), ("jit_other(2)", 40, 50)])
    exposed, total = tr.exposed_permute(c, (0, 100))
    assert exposed == pytest.approx(5e-9)      # 0-2 and 10-13
    assert total == pytest.approx(20e-9)       # the permuting program only


def test_gaps_are_named_by_the_host():
    t = tr.Trace(
        [chip([("%a = a", 0, 10), ("%b = b", 50, 60)], [("jit_x(1)", 0, 60)])],
        [tr.Event("bench.window", 0, 100), tr.Event("bench.call[0]", 0, 70),
         tr.Event("TransferToDevice", 12, 48), tr.Event("Wide", 5, 49)],
        (0, 100))
    assert tr.longest_gaps(t) == [
        ["bench.call[0] > TransferToDevice", 40e-9], ["no host event", 40e-9]]
    ops = tr.device_ops(t)
    assert ops == [["jit_x/a", 10e-9], ["jit_x/b", 10e-9]]


# -- a trace recorded on a TPU v5e: seven host-tier calls at n = 512 with a
# budget that streams them in blocks (the tiny_ooc cell), whose run printed
# block_dgemm_roofline 14.94825353151841 and device_idle_share
# 99.95473717121239.
@pytest.fixture(scope="module")
def tiny_ooc():
    return tr.load(str(DATA / "tiny_ooc.xplane.pb"))


def test_recorded_trace_planes_and_window(tiny_ooc):
    t = tiny_ooc
    assert len(t.chips) == 1
    assert t.window_s == pytest.approx(0.344814949)
    names = {tr.module_name(e.name) for e in t.chips[0].modules}
    assert names == {"jit__block_dgemm", "jit_convert_element_type"}
    assert sum(e.name.startswith("bench.call[") for e in t.host) == 7


def test_recorded_trace_busy_kernel_ops_and_gaps(tiny_ooc):
    t, c = tiny_ooc, tiny_ooc.chips[0]
    busy = tr.busy_seconds(c, t.window)
    assert busy == pytest.approx(0.000156073)
    assert busy <= tr.module_seconds(c, "jit__block_dgemm", t.window) \
        < t.window_s
    ops = tr.device_ops(t)
    assert ops[0][0] == "jit__block_dgemm/fusion"
    assert sum(v for _, v in ops) == pytest.approx(busy)
    gaps = tr.longest_gaps(t)
    assert len(gaps) == 10 and all(g[0].startswith("bench.") for g in gaps)
    assert gaps[0][1] == max(g[1] for g in gaps)
    assert tr.exposed_permute(c, t.window) == (0.0, 0.0)


def test_readers_reproduce_the_recorded_run(tiny_ooc):
    from bench import harness, work

    calls = [harness.Call(i, 0.0, 512, 512, 512, 4, {}, None, None, None)
             for i in range(7)]
    run = harness.Run({"peak_rate": "bfloat16"}, 0.0, 0.0, calls, 0,
                      work.peaks("TPU v5 lite"), tiny_ooc)
    roof = harness.load_reader("block_dgemm_roofline")(run)
    idle = harness.load_reader("device_idle_share")(run)
    assert roof == pytest.approx(14.94825353151841)
    assert run.notes["block_dgemm_roofline"] == "memory-bound"
    assert idle == pytest.approx(99.95473717121239)
    assert harness.load_reader("block_dgemm_roofline.incore")(run) == roof
    assert harness.load_reader("device_idle_share.incore")(run) == idle
    assert harness.load_reader("summa_permute_exposed_share")(run) is None


# -- a trace recorded on four TPU v5e chips: 52 SUMMA calls at n = 512 (the
# tiny_mesh cell), whose run printed device_idle_share.mesh4
# 99.65328158636154.
@pytest.fixture(scope="module")
def tiny_mesh():
    return tr.load(str(DATA / "tiny_mesh.xplane.pb"))


def test_recorded_mesh_trace_permutes_and_control_flow(tiny_mesh):
    t = tiny_mesh
    assert len(t.chips) == 4
    assert {tr.module_name(e.name) for c in t.chips for e in c.modules} \
        == {"jit_convert_element_type", "jit_ring_body"}
    # the ring's while op spans its body: it is neither an op of its own
    # in the breakdown nor "another operation" hiding the permutes
    assert any(tr.is_container(e.name) for e in t.chips[0].ops)
    assert not any(n.split("/")[1].startswith("while")
                   for n, _ in tr.device_ops(t))
    for c in t.chips:
        exposed, total = tr.exposed_permute(c, t.window)
        assert 0 < exposed < total <= tr.busy_seconds(c, t.window)


def test_readers_reproduce_the_recorded_mesh_run(tiny_mesh):
    from bench import harness

    run = harness.Run({}, 0.0, 0.0, [], 0, {}, tiny_mesh)
    idle = harness.load_reader("device_idle_share.mesh4")(run)
    assert idle == pytest.approx(99.65328158636154)
    exposed = harness.load_reader("summa_permute_exposed_share")(run)
    assert exposed == pytest.approx(58.668935734153116)
