"""CPU tests of the chip benchmark: ``python -m pytest bench/tests``.

JAX runs on the CPU with four virtual devices, so the four-chip cell's
path runs here too.  The device count is fixed when JAX starts, so this
file sets it before anything imports JAX.
"""

import json
import os
import shutil
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]

# Cells small enough for a test run, on the committed configurations.
TINY_TRAFFIC = {
    "tiny_ooc": {"n": 512, "mutate_band": 64, "check_groups": 4,
                 "check_rows": 8, "check_calls": 2},
    "tiny_incore": {"n": 256, "mutate_band": 64, "check_groups": 4,
                    "check_rows": 8, "check_calls": 2},
    "tiny_mesh": {"n": 512, "mutate_band": 64, "check_groups": 4,
                  "check_rows": 8, "check_calls": 2},
}
# the committed cell each tiny cell copies; tiny_incore stands for an
# in-core cell of the same configuration, which has none committed
TINY_CELLS = {"tiny_ooc": "mmooc_f32.ooc_n40960",
              "tiny_incore": "mmooc_f32.ooc_n40960"}
# The MESH tier has no committed cell: a configuration of it, added as a
# new file and entries as a later cell would be, keeps its path tested.
SUMMA = {"name": "summa_f32", "backend": "mesh", "chips": 4,
         "dtype": "float32", "precision": "highest",
         "peak_rate": "float32_highest", "alpha": 1.0, "beta": 0.5,
         "budget_divisor": 2, "n_max": 512, "host_slack_gib": 0}
# a bytes_limit whose half keeps tiny_ooc out of core and tiny_incore in
TINY_BYTES_LIMIT = 2 * 2**20


def make_root(dst: Path) -> Path:
    """A checkout with the benchmark's files and the tiny cells added as
    new files and entries."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mmooc = json.loads((ROOT / "bench/configs/mmooc_f32.json").read_text())
    summa = {**SUMMA, "limits": mmooc["limits"]}   # the same comparison
    (dst / "bench/configs/summa_f32.json").write_text(json.dumps(summa))
    spec["configs"].append({"name": "summa_f32", "source": "test",
                            "file": "bench/configs/summa_f32.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "summa_f32.tiny_mesh",
                              "config": "summa_f32", "traffic": "tiny_mesh",
                              "chips": 4, "why": "test"})
    for name, traffic in TINY_TRAFFIC.items():
        (dst / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
        if name not in TINY_CELLS:
            continue
        real = next(w for w in spec["workloads"]
                    if w["name"] == TINY_CELLS[name])
        tiny = f"{real['config']}.{name}"
        spec["workloads"].append({**real, "name": tiny, "traffic": name})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if real["name"] in m.get("workloads", []):
                m["workloads"].append(tiny)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """The tiny checkout, with a device that reports a small memory."""
    from bench import harness

    monkeypatch.setattr(harness, "memory_stats", lambda d: {
        "bytes_limit": TINY_BYTES_LIMIT, "peak_bytes_in_use": 12345})
    return make_root(tmp_path)
