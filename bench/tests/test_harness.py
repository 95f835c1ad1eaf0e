"""The harness on the CPU: its refusals, its data-driven lookup, its
inputs and what it keeps alive."""

import gc
import json
import os
import subprocess
import sys
import weakref

import numpy as np
import pytest

from bench import gen, harness

from conftest import ROOT


def test_command_refuses_a_machine_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mmooc_f32.ooc_n40960", "--seed", "3000000000", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload 'nope'"):
        harness.resolve(harness.load_spec(), "nope")


def test_new_cell_configuration_and_metric_are_found_from_files(tiny_root):
    """A cell, a configuration and a metric added as new files and
    entries, with no edit to a file that was there."""
    conf = json.loads((tiny_root / "bench/configs/mmooc_f32.json")
                      .read_text())
    conf["name"] = "mmooc_f32_copy"
    (tiny_root / "bench/configs/mmooc_f32_copy.json").write_text(
        json.dumps(conf))
    (tiny_root / "bench/traffic/tiny_new.json").write_text(json.dumps(
        {"n": 256, "mutate_band": 64, "check_groups": 2, "check_rows": 8,
         "check_calls": 1}))
    (tiny_root / "bench/metrics/calls_done.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "mmooc_f32_copy", "source": "test",
                            "file": "bench/configs/mmooc_f32_copy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "mmooc_f32_copy.tiny_new",
                              "config": "mmooc_f32_copy",
                              "traffic": "tiny_new", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "calls_done", "unit": "calls",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["mmooc_f32_copy.tiny_new"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = harness.run_workload("mmooc_f32_copy.tiny_new", 1, 0.2, False,
                               root=tiny_root, require_tpu=False)
    assert out["correct"]
    assert out["metrics"]["calls_done"]["value"] \
        == out["attempted"] > 0
    assert set(out["metrics"]) == {"calls_done", "setup_s"}


def test_metrics_follow_their_workload_lists(tiny_root):
    spec = harness.load_spec(tiny_root)
    names = {(w, t): [m["name"] for m in harness.metrics_for(spec, w, t)]
             for w in ("mmooc_f32.ooc_n40960", "summa_f32.tiny_mesh")
             for t in (False, True)}
    assert names[("mmooc_f32.ooc_n40960", False)] == [
        "gemm_tflops", "peak_hbm_gib", "setup_s"]
    assert names[("mmooc_f32.ooc_n40960", True)] == [
        "entry_host_s", "executor_h2d_gib", "block_dgemm_roofline",
        "device_idle_share"]
    # a metric without a workload list is every cell's
    assert names[("summa_f32.tiny_mesh", False)] == ["setup_s"]
    assert names[("summa_f32.tiny_mesh", True)] == []


def test_a_split_metric_has_the_reader_of_its_quantity(tmp_path):
    (tmp_path / "bench/metrics").mkdir(parents=True)
    for name, value in (("q", 1), ("q.own", 2)):
        (tmp_path / f"bench/metrics/{name}.py").write_text(
            f"def read(run):\n    return {value}\n")
    assert harness.load_reader("q.part", tmp_path)(None) == 1
    assert harness.load_reader("q.own", tmp_path)(None) == 2


def test_every_metric_has_a_reader():
    spec = harness.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("shape", [(3000, 17), (1024, 5), (5, 3)])
def test_generator_gives_the_same_values_at_any_thread_count(shape):
    seed = 2**31 + 12345
    one = gen.random_matrix(shape, seed, 1, workers=1)
    assert np.array_equal(one, gen.random_matrix(shape, seed, 1, workers=3))
    assert np.array_equal(one, gen.random_matrix(shape, seed, 1))
    assert not np.array_equal(one, gen.random_matrix(shape, seed, 2))


def test_operands_change_between_calls_and_one_result_stays_alive(
        tiny_root):
    seen, results = [], []

    def factory(config, devices, bytes_limit):
        def call(A, B, C0):
            gc.collect()
            assert all(r() is None for r in results), "two results alive"
            seen.append((A.copy(), B.copy()))
            out = A @ B
            results.append(weakref.ref(out))
            return out, {}
        return call

    out = harness.run_workload("mmooc_f32.tiny_incore", 4, 0.2, False,
                               root=tiny_root, require_tpu=False,
                               call_factory=factory)
    assert out["attempted"] >= 3
    warm, *timed = seen
    for (a0, b0), (a1, b1) in zip(seen, timed):
        # every row block of A and every column block of B differ
        assert np.any(a0 != a1, axis=1).reshape(-1, 64).any(axis=1).all()
        assert np.any(b0 != b1, axis=0).all()


def test_rollback_restores_each_calls_operands():
    A = gen.random_matrix((256, 256), 1, 0)
    B = gen.random_matrix((256, 256), 1, 1)
    A0, B0 = A.copy(), B.copy()
    saved = [gen.mutate(A, B, 1, i, 64) for i in range(3)]
    for s in reversed(saved):
        gen.undo(s)
    assert np.array_equal(A, A0) and np.array_equal(B, B0)


def test_host_ram_guard_refuses_before_making_operands(tiny_root,
                                                       monkeypatch):
    monkeypatch.setattr(harness, "mem_available", lambda: 2**20)
    monkeypatch.setattr(harness, "make_operands", lambda *a, **k: 1 / 0)
    with pytest.raises(SystemExit, match="of host RAM.*never shrunk"):
        harness.run_workload("mmooc_f32.tiny_ooc", 1, 0.1, False,
                             root=tiny_root, require_tpu=False)


def test_a_compile_inside_the_window_is_counted(tiny_root):
    import jax
    import jax.numpy as jnp

    def factory(config, devices, bytes_limit):
        def call(A, B, C0):
            # a new shape every call: compiles inside the window
            k = len(sizes)
            sizes.append(k)
            jax.jit(lambda x: x * 2)(jnp.zeros(k + 1)).block_until_ready()
            return A @ B, {}
        return call

    sizes = []
    out = harness.run_workload("mmooc_f32.tiny_incore", 4, 0.2, False,
                               root=tiny_root, require_tpu=False,
                               call_factory=factory)
    assert out["window_compiles"] >= out["attempted"] > 0
    sound = harness.run_workload("mmooc_f32.tiny_ooc", 4, 0.2, False,
                                 root=tiny_root, require_tpu=False)
    assert sound["window_compiles"] == 0


def test_result_line_has_the_contract_keys_and_compared_last(tiny_root):
    out = harness.run_workload("mmooc_f32.tiny_ooc", 2**31 + 7, 0.2, True,
                               root=tiny_root, require_tpu=False)
    r = out
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in r
    assert list(r)[-1] == "compared"
    assert set(r["compared"]["max_row_rel_err"]) == {"value", "limit"}
    assert set(r["metrics"]) == {"entry_host_s", "executor_h2d_gib"}
    assert r["metrics"]["executor_h2d_gib"]["value"] > 0
    json.loads(json.dumps(r))
