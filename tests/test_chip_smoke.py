"""chip_smoke.py's phases at tiny sizes on the CPU, and its refusals.

The phases are the script's own functions; only the sizes shrink and the
VMEM tier runs in Pallas interpret mode, which the CPU needs and which the
script never chooses by itself.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_ooc_host_phase_streams_above_the_limit():
    n = 384
    rec = chip_smoke.ooc_host_phase(n, 3 * n * n * 4 - 1, seed=0)
    assert rec["h"] * rec["w"] > 1          # out of core: several blocks
    assert rec["h2d_bytes"] > 0 and rec["d2h_bytes"] == n * n * 4
    assert rec["rel_err"] <= 1e-6 and rec["warm_equals_cold"]
    assert rec["checked_rows"] == n


def test_ooc_host_phase_refuses_operands_that_fit():
    n = 128
    with pytest.raises(AssertionError, match="not an out-of-core run"):
        chip_smoke.ooc_host_phase(n, 3 * n * n * 4, seed=0)


def test_in_core_phase():
    rec = chip_smoke.in_core_phase(256, seed=1)
    assert rec["rel_err"] <= 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vmem_phase_interpreted(dtype):
    rec = chip_smoke.vmem_phase(256, dtype, seed=2, interpret=True)
    assert rec["interpret"] and not rec["tpu_custom_call"]
    assert rec["rel_err"] <= rec["tol"]


def test_vmem_phase_will_not_interpret_unasked():
    with pytest.raises(RuntimeError, match="interpret=True"):
        chip_smoke.vmem_phase(256, jnp.float32, seed=2)


def test_mesh_phase_on_four_virtual_devices():
    """The MESH phase on four CPU devices, in a child process: the device
    count is fixed when JAX starts."""
    code = ("import jax, json, chip_smoke; "
            "print(json.dumps(chip_smoke.mesh_phase(256, 0, "
            "jax.devices()[:4])))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4 and rec["collective_permute"]
    assert rec["rel_err"] <= 1e-6


def test_main_refuses_a_machine_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out and out.out == ""
    assert "no TPU" in out.err


def test_random_matrix_is_seeded_and_normal():
    a = chip_smoke.random_matrix((2048, 64), seed=3, stream=0)
    assert np.array_equal(a, chip_smoke.random_matrix((2048, 64), 3, 0))
    assert not np.array_equal(a, chip_smoke.random_matrix((2048, 64), 3, 1))
    assert a.dtype == np.float32 and abs(a.std() - 1) < 0.05


def test_compile_cache_dir(monkeypatch):
    env = {compile_cache.ENV_VAR: "/elsewhere/cache"}
    assert compile_cache.compile_cache_dir(env) == "/elsewhere/cache"
    default = Path(compile_cache.compile_cache_dir({}))
    assert default == ROOT / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        # a set variable is JAX's own to read: nothing else is configured
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV_VAR)
        assert compile_cache.enable_compile_cache() == str(default)
        assert jax.config.jax_compilation_cache_dir == str(default)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
