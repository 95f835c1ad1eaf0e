"""The comparison that decides ``correct``: sound runs pass it, the
control fails it, and so does a run with the timed path broken underneath
in each way the cell can break.  The harness's look for a chip is skipped;
everything else is a whole run of a small cell."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.control import make_control_call
from repro.core import oocgemm, runtime

CELLS = ["mmooc_f32.tiny_ooc", "mmooc_f32.tiny_incore", "summa_f32.tiny_mesh"]


def run(root, workload, **kw):
    return harness.run_workload(workload, 2**31 + 99, 0.2, False, root=root,
                                require_tpu=False, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny_root, workload):
    r = run(tiny_root, workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, workload):
    r = run(tiny_root, workload, call_factory=make_control_call)
    assert not r["correct"]
    cmp = r["compared"]["max_row_rel_err"]
    assert cmp["value"] > cmp["limit"]
    assert cmp["value"] < 1e-4   # three bf16 passes' error, not one pass's


# -- faults planted in the timed path ----------------------------------------
def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _half(a, b):
    """Half of the inner dimension left out, the rest counted double."""
    k = a.shape[1] // 2
    return 2 * _dot(a[:, :k], b[:k])


def block(product=_dot, unchanged=False, roll=False):
    def fn(a, b, c, alpha, beta, transpose=False):
        if unchanged:
            return c
        out = (alpha * product(a, b) + beta * c).astype(c.dtype)
        return jnp.roll(out, 1, axis=0) if roll else out
    return fn


class Jnp:
    """``jax.numpy`` with another ``dot``."""

    def __init__(self, dot):
        self.dot = dot

    def __getattr__(self, name):
        return getattr(jnp, name)


@pytest.fixture
def fresh_summa():
    runtime._summa_program.cache_clear()
    yield
    runtime._summa_program.cache_clear()


def host_fault(monkeypatch, fn):
    monkeypatch.setattr(runtime, "_block_dgemm", fn)
    monkeypatch.setattr(oocgemm, "_block_dgemm", fn)


def mesh_dot(monkeypatch, dot):
    monkeypatch.setattr(runtime, "jnp", Jnp(
        lambda a, b, **kw: dot(a, b)))


HOST_FAULTS = {
    "unchanged": lambda mp: host_fault(mp, block(unchanged=True)),
    "half": lambda mp: host_fault(mp, block(product=_half)),
    "altered": lambda mp: host_fault(mp, block(roll=True)),
}
MESH_FAULTS = {
    "unchanged": lambda mp: mp.setattr(
        runtime.MeshOocRuntime, "gemm",
        lambda self, A, B, C, alpha, beta, part=None, **kw:
        jax.device_put(C, self.shardings()[2])),
    "half": lambda mp: mesh_dot(mp, _half),
    "no_exchange": lambda mp: mp.setattr(
        jax.lax, "ppermute", lambda x, axis_name, perm: x),
    "altered": lambda mp: mesh_dot(
        mp, lambda a, b: jnp.roll(_dot(a, b), 1, axis=0)),
}
FAULTS = ([(w, f) for w in CELLS[:2] for f in HOST_FAULTS]
          + [(CELLS[2], f) for f in MESH_FAULTS])


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch,
                                          fresh_summa, workload, fault):
    faults = MESH_FAULTS if workload.startswith("summa") else HOST_FAULTS
    faults[fault](monkeypatch)
    r = run(tiny_root, workload)
    assert not r["correct"]
    assert r["failed"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_calibration_separates_program_from_control(tiny_root, workload):
    from bench.calibrate import calibrate

    got = calibrate(workload, [1, 2**31 + 5], [3, 4], root=tiny_root,
                    require_tpu=False)
    limit = harness.resolve(harness.load_spec(tiny_root), workload,
                            tiny_root)[1]["limits"]["max_row_rel_err"]
    assert max(got["program"]) < limit < min(got["control"])
    assert min(got["control"]) >= 3 * max(got["program"])
