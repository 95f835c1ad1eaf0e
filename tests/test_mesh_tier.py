"""The MESH tier (``ooc_gemm(..., backend="mesh")``) against the plain
reference, on four virtual CPU devices.

The device count is fixed when JAX starts, so the tier runs in one child
process (this file run as a script) that prints what it saw as one JSON
object; the tests below check that object, case by case.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
DEVICES = 4
# (M, N, K, alpha, beta, with C): the benchmark cell's alpha and beta on a
# square problem, a rectangular one, C left out, and alpha, beta not 1
CASES = {
    "square": (256, 256, 256, 1.0, 0.5, True),
    "rectangular": (128, 256, 384, 1.0, 0.5, True),
    "c_none": (256, 256, 256, 1.0, 0.5, False),
    "alpha_beta": (256, 256, 256, -0.75, 2.5, True),
}
# operands the ring cannot shard: (A's shape, B's shape, the refusal)
REFUSALS = {
    "inner_dims": ((256, 128), (256, 256),
                   r"inner dims mismatch: \(256, 128\) @ \(256, 256\)"),
    "m_not_divisible": ((254, 256), (256, 256),
                        f"SUMMA needs M,N divisible by mesh axis {DEVICES}"),
    "n_not_divisible": ((256, 256), (256, 254),
                        f"SUMMA needs M,N divisible by mesh axis {DEVICES}"),
}


def _operands(M, N, K, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((M, K), dtype=np.float32),
            rng.standard_normal((K, N), dtype=np.float32),
            rng.standard_normal((M, N), dtype=np.float32))


def _child() -> dict:
    """Every case on a mesh of the four devices; what each one returned,
    its errors against the references in units of the summation-order
    bound, and the runtime's counters and spans."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.core import MeshOocRuntime, ooc_gemm
    from repro.kernels import ref
    from repro.obs import get_observability

    mesh = jax.make_mesh((DEVICES,), ("model",), devices=jax.devices(),
                         axis_types=(AxisType.Auto,))
    rt = MeshOocRuntime(mesh)
    obs = get_observability()
    placed = []

    def program(overlap=True):
        # the arrays the ring is handed, as placed
        run = MeshOocRuntime.program(rt, overlap)

        def seen(a, b, c, alpha, beta):
            placed.append([x.sharding == s and x.sharding.spec == s.spec
                           for x, s in zip((a, b, c), rt.shardings())])
            return run(a, b, c, alpha, beta)
        return seen

    rt.program = program
    out = {"devices": len(jax.devices()), "cases": {}}
    for i, (name, (M, N, K, alpha, beta, with_c)) in enumerate(
            CASES.items()):
        A, B, C = _operands(M, N, K, i)
        kept = [x.copy() for x in (A, B, C)]
        budget = rt.working_set_bytes(M, N, K, 4)
        placed.clear()
        obs.start_trace()
        try:
            got = ooc_gemm(A, B, C if with_c else None, alpha, beta,
                           budget_bytes=budget, backend="mesh", runtime=rt)
        finally:
            tracer = obs.stop_trace()
        c = C if with_c else np.zeros((M, N), np.float32)
        bound = ref.gemm_error_bound(A, B, c, alpha, beta)
        want = np.asarray(ref.gemm_ref(jnp.asarray(A), jnp.asarray(B),
                                       jnp.asarray(c), alpha, beta))
        exact = alpha * (A.astype(np.float64) @ B) + beta * c
        spans = sorted(tracer.spans(), key=lambda s: s.start)
        ids = {s.span_id: s.name for s in spans}
        out["cases"][name] = {
            "type": type(got).__name__, "shape": list(got.shape),
            "writeable": bool(got.flags.writeable),
            "ref_err": float(np.max(np.abs(got - want) / (2 * bound))),
            "f64_err": float(np.max(np.abs(got - exact) / bound)),
            "place_bytes": rt.last_place_bytes,
            "gather_bytes": rt.last_gather_bytes,
            "place_transfers": rt.last_place_transfers,
            "placed_in_shardings": list(placed),
            "want_place_bytes": A.nbytes + B.nbytes + c.nbytes,
            "want_gather_bytes": M * N * 4,
            "operands_kept": all(np.array_equal(x, y)
                                 for x, y in zip((A, B, C), kept)),
            "spans": [[s.name, ids.get(s.parent_id), dict(s.args)]
                      for s in spans],
            "times": [[s.name, s.start, s.end] for s in spans]}
    host = _operands(256, 256, 256, 9)
    # already in the program's shardings: A is used as it is, B and C are
    # copied, since the program donates them
    A, B, C = (jax.device_put(x, s) for x, s in zip(host, rt.shardings()))
    got = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=2**30, backend="mesh",
                   runtime=rt)
    out["device_operands"] = {
        "type": type(got).__name__,
        "on_devices": len(got.sharding.device_set),
        "gather_bytes": rt.last_gather_bytes,
        "place_transfers": rt.last_place_transfers,
        "operands_kept": all(not x.is_deleted()
                             and np.array_equal(np.asarray(x), y)
                             for x, y in zip((A, B, C), host))}
    need = rt.working_set_bytes(256, 256, 256, 4)
    A, B, C = _operands(256, 256, 256, 10)
    try:
        ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=need - 1, backend="mesh",
                 runtime=rt)
        out["over_budget"] = None
    except ValueError as e:
        out["over_budget"] = {"need": need, "budget": need - 1,
                              "message": str(e)}
    out["refusals"] = {}
    for name, (a_shape, b_shape, _) in REFUSALS.items():
        try:
            ooc_gemm(np.ones(a_shape, np.float32),
                     np.ones(b_shape, np.float32), None, 1.0, 0.0,
                     budget_bytes=2**30, backend="mesh", runtime=rt)
            out["refusals"][name] = None
        except Exception as e:  # its type is checked below
            out["refusals"][name] = [type(e).__name__, str(e)]
    return out


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={DEVICES}",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["devices"] == DEVICES
    return rec


@pytest.mark.parametrize("case", CASES)
def test_mesh_tier_matches_the_plain_reference(child, case):
    got = child["cases"][case]
    assert got["ref_err"] <= 1.0       # gemm_ref, summed in another order
    assert got["f64_err"] <= 1.0       # NumPy float64


@pytest.mark.parametrize("case", CASES)
def test_host_operands_give_a_host_result(child, case):
    got = child["cases"][case]
    M, N = CASES[case][:2]
    assert got["type"] == "ndarray" and got["writeable"]
    assert got["shape"] == [M, N]


@pytest.mark.parametrize("case", CASES)
def test_the_callers_operands_are_left_as_they_were(child, case):
    # the ring donates its own copies of B and C, never the caller's
    assert child["cases"][case]["operands_kept"]


@pytest.mark.parametrize("case", CASES)
def test_place_and_gather_bytes_are_exact(child, case):
    got = child["cases"][case]
    assert got["place_bytes"] == got["want_place_bytes"]
    assert got["gather_bytes"] == got["want_gather_bytes"]


@pytest.mark.parametrize("case", CASES)
def test_mesh_spans_in_order_under_the_call(child, case):
    got = child["cases"][case]
    puts = [s for s in got["spans"] if s[0] == "ooc.mesh.put"]
    assert [s for s in got["spans"] if s[0] != "ooc.mesh.put"] == [
        ["ooc.gemm", None, {}],
        ["ooc.mesh.place", "ooc.gemm",
         {"bytes": str(got["want_place_bytes"])}],
        ["ooc.mesh.ring", "ooc.gemm", {}],
        ["ooc.mesh.gather", "ooc.gemm",
         {"bytes": str(got["want_gather_bytes"])}]]
    assert got["spans"][2:2 + len(puts)] == puts
    assert all(s[1] == "ooc.mesh.place" for s in puts)


@pytest.mark.parametrize("case", CASES)
def test_host_operands_are_placed_one_transfer_at_a_time(child, case):
    # A, B and C, one shard a chip each: twelve transfers, each inside
    # the placement and landed before the next begins
    got = child["cases"][case]
    assert got["place_transfers"] == 3 * DEVICES
    puts = [t for t in got["times"] if t[0] == "ooc.mesh.put"]
    (place,) = [t for t in got["times"] if t[0] == "ooc.mesh.place"]
    assert len(puts) == got["place_transfers"]
    assert all(place[1] <= t[1] <= t[2] <= place[2] for t in puts)
    assert all(a[2] <= b[1] for a, b in zip(puts, puts[1:]))


@pytest.mark.parametrize("case", CASES)
def test_put_spans_carry_every_placed_byte(child, case):
    got = child["cases"][case]
    puts = [s[2] for s in got["spans"] if s[0] == "ooc.mesh.put"]
    assert sum(int(p["bytes"]) for p in puts) == got["place_bytes"]
    # each chip is sent its shard of A, of B and of C
    devices = sorted(int(p["device"]) for p in puts)
    assert devices == sorted(list(range(DEVICES)) * 3)


@pytest.mark.parametrize("case", CASES)
def test_placed_arrays_carry_the_programs_shardings(child, case):
    assert child["cases"][case]["placed_in_shardings"] == [[True] * 3]


def test_device_operands_give_a_sharded_device_result(child):
    got = child["device_operands"]
    assert got["type"] == "ArrayImpl" and got["on_devices"] == DEVICES
    assert got["gather_bytes"] == 0
    assert got["operands_kept"]


def test_device_operands_make_no_host_transfer(child):
    assert child["device_operands"]["place_transfers"] == 0


def test_a_budget_below_the_working_set_raises(child):
    got = child["over_budget"]
    assert got is not None, "an over-budget call ran"
    assert str(got["need"]) in got["message"]
    assert str(got["budget"]) in got["message"]


@pytest.mark.parametrize("case", REFUSALS)
def test_operands_the_ring_cannot_shard_are_refused(child, case):
    got = child["refusals"][case]
    assert got is not None, "the call ran"
    assert got[0] == "ValueError"
    assert re.search(REFUSALS[case][2], got[1]), got[1]


if __name__ == "__main__":
    print(json.dumps(_child()))
