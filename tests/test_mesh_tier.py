"""The MESH tier (``ooc_gemm(..., backend="mesh")``) against the plain
reference, on four virtual CPU devices.

The device count is fixed when JAX starts, so the tier runs in one child
process (this file run as a script) that prints what it saw as one JSON
object; the tests below check that object, case by case.  The child also
runs the scenarios of the runtime's kept result (``RECYCLED``) under one
profiler session, and reads back which result span each gather holds.
"""

from __future__ import annotations

import glob
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

# scenarios of the runtime's kept result, each started from a runtime that
# keeps nothing, and what each gather of the scenario gets, in order
RECYCLED = {
    "dropped": ("alloc", "reuse"),
    "held": ("alloc", "alloc"),
    "view": ("alloc", "alloc"),
    "passed_as_c": ("alloc", "alloc", "alloc"),
    "new_shape": ("alloc", "alloc"),
    "released": ("alloc", "alloc"),
    "no_runtime": ("alloc", "alloc"),
    "device_operands": (),
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
    out["recycled"] = _recycled(mesh)
    return out


def _ptr(a: np.ndarray) -> int:
    return a.__array_interface__["data"][0]


def _recycled(mesh) -> dict:
    """Every ``RECYCLED`` scenario on one runtime, each inside a
    ``probe.<scenario>`` annotation of one profiler session: what the
    scenario saw, each call's error against float64 in units of the
    summation-order bound, and, per gather of the scenario, the names of
    the ``ooc.mesh.*_result`` spans inside it."""
    import tempfile
    import weakref

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.core import MeshOocRuntime, ooc_gemm
    from repro.kernels import ref

    rt = MeshOocRuntime(mesh)
    seeds = iter(range(100, 200))
    seen = {name: {"errs": []} for name in RECYCLED}

    def call(name, M=256, C=None, runtime=rt):
        A, B, C0 = _operands(M, 256, 256, next(seeds))
        C = C0 if C is None else C
        want = A.astype(np.float64) @ B + 0.5 * np.asarray(C, np.float64)
        got = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=2**30,
                       backend="mesh", mesh=mesh, runtime=runtime)
        bound = ref.gemm_error_bound(A, B, C, 1.0, 0.5)
        seen[name]["errs"].append(float(np.max(np.abs(got - want) / bound)))
        return got

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with TraceAnnotation("probe.dropped"):
                rt.release()
                first = call("dropped")
                kept, at = weakref.ref(first), _ptr(first)
                del first
                second = call("dropped")
                seen["dropped"].update(same_array=second is kept(),
                                       same_pointer=_ptr(second) == at)
                del second
            for name in ("held", "view"):
                with TraceAnnotation(f"probe.{name}"):
                    rt.release()
                    held = call(name)
                    if name == "view":
                        held = held[:3]      # the result itself dropped
                    before = held.copy()
                    other = call(name)
                    seen[name].update(
                        unchanged=bool(np.array_equal(held, before)),
                        shares=bool(np.shares_memory(held, other)))
                    del held, other
            with TraceAnnotation("probe.passed_as_c"):
                # the caller's loop C = ooc_gemm(A, B, C, ...): the C of a
                # call is alive until it returns, so never written into
                rt.release()
                C = call("passed_as_c")
                moved = []
                for _ in range(2):
                    at = _ptr(C)
                    C = call("passed_as_c", C=C)
                    moved.append(_ptr(C) != at)
                seen["passed_as_c"]["moved"] = moved
                del C
            with TraceAnnotation("probe.new_shape"):
                rt.release()
                r = call("new_shape")
                gone = weakref.ref(r)
                del r
                kept = gone() is not None
                other = call("new_shape", M=128)
                seen["new_shape"].update(kept_until_next_call=kept,
                                         dropped=gone() is None,
                                         shape=list(other.shape))
                del other
            with TraceAnnotation("probe.released"):
                rt.release()
                r = call("released")
                gone = weakref.ref(r)
                del r
                kept = gone() is not None
                rt.release()
                seen["released"].update(kept_until_release=kept,
                                        dropped=gone() is None)
                call("released")
            with TraceAnnotation("probe.no_runtime"):
                r = call("no_runtime", runtime=None)
                gone = weakref.ref(r)
                del r
                seen["no_runtime"]["dropped"] = gone() is None
                call("no_runtime", runtime=None)
            with TraceAnnotation("probe.device_operands"):
                rt.release()
                A, B, C = (jax.device_put(x, s) for x, s in zip(
                    _operands(256, 256, 256, next(seeds)), rt.shardings()))
                got = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=2**30,
                               backend="mesh", runtime=rt)
                seen["device_operands"].update(
                    type=type(got).__name__,
                    on_devices=len(got.sharding.device_set),
                    kept=rt._idle is not None)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        events = [(e.name.split("#", 1)[0], int(e.start_ns), int(e.end_ns))
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith(("probe.", "ooc.mesh."))]

    def within(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    results = [e for e in events if e[0].endswith("_result")]
    for name in RECYCLED:
        probe, = [e for e in events if e[0] == f"probe.{name}"]
        gathers = sorted(e for e in events if e[0] == "ooc.mesh.gather"
                         and within(e, probe))
        seen[name]["gathers"] = [
            [r[0] for r in sorted(results, key=lambda r: r[1])
             if within(r, g)] for g in gathers]
        seen[name]["results_outside_gathers"] = sum(
            within(r, probe) and not any(within(r, g) for g in gathers)
            for r in results)
    return seen


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


@pytest.mark.parametrize("scenario", RECYCLED)
def test_recycled_results_match_the_plain_reference(child, scenario):
    got = child["recycled"][scenario]
    assert len(got["errs"]) == len(RECYCLED[scenario])
    assert all(e <= 1.0 for e in got["errs"]), got["errs"]


@pytest.mark.parametrize("scenario", RECYCLED)
def test_one_result_span_a_call_inside_its_gather(child, scenario):
    got = child["recycled"][scenario]
    assert got["gathers"] == [[f"ooc.mesh.{choice}_result"]
                              for choice in RECYCLED[scenario]]
    assert got["results_outside_gathers"] == 0


def test_a_dropped_result_is_gathered_into_again(child):
    got = child["recycled"]["dropped"]
    assert got["same_array"] and got["same_pointer"]


@pytest.mark.parametrize("scenario", ("held", "view"))
def test_a_held_result_or_view_is_never_written(child, scenario):
    got = child["recycled"][scenario]
    assert got["unchanged"] and not got["shares"]


def test_a_result_passed_back_as_c_is_never_written_into(child):
    assert child["recycled"]["passed_as_c"]["moved"] == [True, True]


def test_a_new_shape_drops_the_kept_result(child):
    got = child["recycled"]["new_shape"]
    assert got["kept_until_next_call"] and got["dropped"]
    assert got["shape"] == [128, 256]


def test_release_drops_the_kept_result(child):
    got = child["recycled"]["released"]
    assert got["kept_until_release"] and got["dropped"]


def test_without_a_runtime_nothing_is_kept(child):
    assert child["recycled"]["no_runtime"]["dropped"]


def test_device_operands_leave_the_runtime_keeping_nothing(child):
    got = child["recycled"]["device_operands"]
    assert got["type"] == "ArrayImpl" and got["on_devices"] == DEVICES
    assert not got["kept"]


if __name__ == "__main__":
    print(json.dumps(_child()))
