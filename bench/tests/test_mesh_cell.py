"""The MESH tier's cell, ``summa_f32.mesh4_n40960``: its entries, its
comparison (sound runs, planted faults and the calibration of the tiny mesh
cell on four CPU devices, at a budget the tiny cell fits), and the readers
of its spans and of its roofline, on a traced tiny run and on a trace made
by hand."""

import json

import jax
import numpy as np
import pytest

from bench import harness, work
from bench import trace as tr

from conftest import ROOT
from test_correct import MESH_FAULTS, fresh_summa, run  # noqa: F401

CELL = "summa_f32.mesh4_n40960"
SPANS = ("mesh_place_s", "mesh_ring_s", "mesh_gather_s")
DEVICE = ("summa_roofline", "summa_permute_exposed_share")


def test_committed_cell_resolves_with_its_metric_lists():
    spec = harness.load_spec()
    cell, config, traffic = harness.resolve(spec, CELL)
    assert cell["chips"] == 4 and config["backend"] == "mesh"
    assert config["chips"] == 4 and traffic["n"] == config["n_max"] == 40960
    assert [m["name"] for m in harness.metrics_for(spec, CELL, False)] == [
        "gemm_tflops", "peak_hbm_gib", "setup_s"]
    assert [m["name"] for m in harness.metrics_for(spec, CELL, True)] == [
        "device_idle_share", *SPANS, *DEVICE]
    conf = json.loads((ROOT / "bench/configs/summa_f32.json").read_text())
    mmooc = json.loads((ROOT / "bench/configs/mmooc_f32.json").read_text())
    assert conf["limits"] == mmooc["limits"]


def test_the_tiny_checkout_runs_the_test_configuration(tiny_root):
    """conftest's tiny summa_f32 is written where the committed entry
    points, so the tiny mesh cell runs at its tiny size."""
    spec = harness.load_spec(tiny_root)
    _, config, traffic = harness.resolve(spec, "summa_f32.tiny_mesh",
                                         tiny_root)
    assert config["n_max"] == traffic["n"] == 512
    with pytest.raises(ValueError, match="over the configuration's n_max"):
        harness.resolve(spec, CELL, tiny_root)


def with_tiny_mesh_metrics(root):
    """The tiny mesh cell added to the lists of the committed cell's
    metrics, as conftest adds the other tiny cells."""
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("summa_f32.tiny_mesh")
    path.write_text(json.dumps(spec))
    return root


@pytest.fixture
def roomy_root(tiny_root, monkeypatch):
    """The tiny checkout on a device whose budget the tiny mesh cell fits:
    conftest's bytes_limit leaves 1 MiB a chip, under the tiny cell's
    1.0625 MiB (four 256 KiB blocks and a 64 KiB product), which the
    tier refuses."""
    monkeypatch.setattr(harness, "memory_stats", lambda d: {
        "bytes_limit": 4 * 2**20, "peak_bytes_in_use": 12345})
    return tiny_root


def test_the_tiny_mesh_cell_is_refused_over_its_budget(tiny_root):
    with pytest.raises(ValueError, match="over budget_bytes = 1048576"):
        run(tiny_root, "summa_f32.tiny_mesh")


def test_sound_tiny_mesh_run_is_correct(roomy_root):
    r = run(roomy_root, "summa_f32.tiny_mesh")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault", sorted(MESH_FAULTS))
def test_broken_mesh_path_is_not_correct(roomy_root, monkeypatch,
                                         fresh_summa, fault):
    """Each way the ring can break (C unchanged, half of K, no exchange
    between chips, rows shifted) fails the comparison."""
    MESH_FAULTS[fault](monkeypatch)
    r = run(roomy_root, "summa_f32.tiny_mesh")
    assert not r["correct"]
    assert r["failed"] > 0


def test_tiny_mesh_calibration_separates_program_from_control(roomy_root):
    from bench.calibrate import calibrate

    got = calibrate("summa_f32.tiny_mesh", [1, 2**31 + 5], [3, 4],
                    root=roomy_root, require_tpu=False)
    limit = harness.resolve(harness.load_spec(roomy_root),
                            "summa_f32.tiny_mesh",
                            roomy_root)[1]["limits"]["max_row_rel_err"]
    assert max(got["program"]) < limit < min(got["control"])
    assert min(got["control"]) >= 3 * max(got["program"])


def test_traced_cpu_mesh_run_reports_the_three_spans(roomy_root):
    root = with_tiny_mesh_metrics(roomy_root)
    lines = []
    out = harness.run_workload("summa_f32.tiny_mesh", 2**31 + 17, 0.3, True,
                               root=root, require_tpu=False,
                               log=lines.append)
    assert out["correct"]
    # no chip on the CPU: the device trace's metrics read nothing
    assert set(out["metrics"]) == set(SPANS)
    got = {m: out["metrics"][m]["value"] for m in SPANS}
    assert all(v > 0 for v in got.values()), got
    line = next(x for x in lines if x.startswith("calls: "))
    walls = [float(w) for w in line.split("walls ")[1].split(";")[0].split()]
    mean_wall = sum(walls) / len(walls)       # printed to the microsecond
    assert 0.5 * mean_wall < sum(got.values()) <= mean_wall + 1e-6


def test_the_roofline_reads_the_program_the_tier_compiles():
    from jax.sharding import AxisType

    from repro.core import MeshOocRuntime

    reader = harness.load_reader("summa_roofline")
    program = reader.__globals__["PROGRAM"]
    mesh = jax.make_mesh((4,), ("model",), devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,))
    rt = MeshOocRuntime(mesh)
    shapes = [jax.ShapeDtypeStruct((256, 256), np.float32, sharding=s)
              for s in rt.shardings()]
    text = rt.program().lower(*shapes, np.float32(1), np.float32(0)).as_text()
    assert f"module @{program} " in text


def mesh_run(module_ns, calls=2, n=4096, module="jit_summa_ring"):
    """A run of ``calls`` calls at ``n`` on four chips, chip ``i`` running
    ``module`` for ``module_ns[i]`` ns in all."""
    chips = [tr.Chip([], [tr.Event(f"{module}(7)", 0, t)])
             for t in module_ns]
    trace = tr.Trace(chips, [tr.Event(tr.WINDOW, 0, 10**12)], (0, 10**12))
    config = {"chips": 4, "peak_rate": "float32_highest"}
    return harness.Run(config, 0.0, 1.0, [
        harness.Call(i, 0.0, n, n, n, 4, {}, None, None, None)
        for i in range(calls)], 0, work.peaks("TPU v5 lite"), trace)


def test_roofline_is_one_chips_work_over_the_slowest_chip():
    peaks = work.peaks("TPU v5 lite")
    n, calls = 4096, 2
    least = calls * work.gemm_flops(n // 4, n, n) \
        / peaks["flop_per_s"]["float32_highest"]
    slow = least / 0.8
    run = mesh_run([int(least * 1e9 / 0.9)] * 3 + [int(slow * 1e9)])
    reader = harness.load_reader("summa_roofline")
    assert reader(run) == pytest.approx(80.0, rel=1e-6)
    assert run.notes["summa_roofline"] == "compute-bound"
    # a chip's time that leaves out work is refused, not capped
    with pytest.raises(ValueError, match="over 105"):
        reader(mesh_run([int(least * 1e9 / 2)] * 4))


def test_roofline_reads_nothing_without_the_program():
    reader = harness.load_reader("summa_roofline")
    assert reader(mesh_run([10**9] * 4, module="jit_ring_body")) is None
    assert reader(harness.Run({}, 0.0, 0.0, [], 0, {}, None)) is None
