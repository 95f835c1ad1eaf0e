#!/usr/bin/env python3
"""Smoke run of the out-of-core GEMM engine on a TPU.  Not a benchmark.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the MESH tier only

One chip runs these phases in order, each through ``ooc_gemm``:

1. host tier: C = A @ B + 0.5 C with M = N = K = 40960 in float32.  A, B
   and C take 18.75 GiB, more than the chip's HBM, so blocks stream from
   host memory under a budget of half the device's ``bytes_limit``;
2. in-core: the same call at N = 8192 with a budget that holds it;
3. VMEM tier: the compiled Pallas kernel at N = 8192, in float32 and in
   bf16 with float32 accumulation.

``--chips 4`` runs only the MESH tier's SUMMA ring at N = 32768 on a
four-device mesh.  Every phase checks its result against a float32
reference and prints one JSON line; times in it are smoke-run wall
seconds ending with the result in host memory.  The last line is
``{"ok": true, "device": {...}}`` only if every phase passed.  Without a
TPU the script exits non-zero and runs nothing: there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (HostOocRuntime, MeshOocRuntime,  # noqa: E402
                        VmemOocRuntime, is_in_core, ooc_gemm,
                        plan_gemm_partition)

ALPHA, BETA = 1.0, 0.5

# Relative Frobenius error limits against float32 references.  Every tier
# multiplies float32 blocks with jnp.dot at DEFAULT precision, XLA's and
# Mosaic's alike: on a TPU one bf16 MXU pass with float32 accumulation, so
# each operand carries ~2^-9 relative rounding and normal data gives an
# error near 2.4e-3.
TOL_DEFAULT_F32 = 1e-2
# The VMEM tier's bf16 kernel: its products are exact in float32 and only
# the summation order differs from the reference, which sees the same bf16
# inputs.
TOL_VMEM_BF16 = 1e-4


def random_matrix(shape, seed: int, stream: int) -> np.ndarray:
    """Standard-normal float32 from ``(seed, stream)``, filled in parallel
    row chunks, each from its own child seed (same values at any thread
    count)."""
    out = np.empty(shape, np.float32)
    chunk = 1024
    seqs = np.random.SeedSequence([seed, stream]).spawn(
        -(-shape[0] // chunk))

    def fill(i):
        np.random.default_rng(seqs[i]).standard_normal(
            out=out[i * chunk:(i + 1) * chunk], dtype=np.float32)

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(fill, range(len(seqs))))
    return out


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(phase: str, err: float, tol: float) -> None:
    if not err <= tol:
        raise AssertionError(
            f"{phase}: relative Frobenius error {err!r} above {tol}")


def sampled_rows(n: int, seed: int, samples: int = 4,
                 rows: int = 128) -> np.ndarray:
    """Indices of ``samples`` row blocks of ``rows`` full-width rows."""
    rows = min(rows, n)
    starts = np.random.default_rng(seed).choice(
        n // rows, size=min(samples, n // rows), replace=False)
    return np.concatenate([np.arange(s * rows, (s + 1) * rows)
                           for s in np.sort(starts)])


def host_ref_rows(A, B, C0, idx) -> np.ndarray:
    """``alpha * A[idx] @ B + beta * C0[idx]`` in host float32."""
    return ALPHA * (A[idx] @ B) + BETA * C0[idx]


def mem_available() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("no MemAvailable in /proc/meminfo")


def host_bytes_needed(part) -> int:
    """Host RAM the host-tier phase takes after JAX has started: A, B, C0
    and the result, two C blocks landing from the device and 1 GiB for the
    process.  H2D staging goes through the TPU runtime's premapped buffer,
    which JAX start-up has already taken."""
    n2 = part.M * part.N * part.bytes_per_el
    return 4 * n2 + 2 * part.bm * part.bn * part.bytes_per_el + 2**30


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def ooc_host_phase(n: int, bytes_limit: int, seed: int) -> dict:
    """Out-of-core GEMM on the host-streaming HBM tier, operands above
    ``bytes_limit``, budget half of it."""
    bpe = 4
    operand_bytes = 3 * n * n * bpe
    if operand_bytes <= bytes_limit:
        raise AssertionError(
            f"operands of {operand_bytes} B fit the device's {bytes_limit} "
            f"B: not an out-of-core run")
    budget = bytes_limit // 2
    part = plan_gemm_partition(n, n, n, budget, bpe)
    need, avail = host_bytes_needed(part), mem_available()
    if avail < need:
        raise RuntimeError(
            f"host-tier phase needs {need / 2**30:.1f} GiB of host RAM, "
            f"{avail / 2**30:.1f} GiB available")
    A, B, C0 = (random_matrix((n, n), seed, s) for s in range(3))
    idx = sampled_rows(n, seed)
    rt = HostOocRuntime()

    def run():
        return ooc_gemm(A, B, C0, ALPHA, BETA, budget_bytes=budget,
                        backend="host", runtime=rt)

    out, cold = timed(run)
    got_cold = out[idx].copy()
    del out
    out, warm = timed(run)
    got_warm = out[idx].copy()
    del out
    want = host_ref_rows(A, B, C0, idx)
    err = rel_err(got_cold, want)
    check("ooc_host", err, TOL_DEFAULT_F32)
    check("ooc_host warm", rel_err(got_warm, want), TOL_DEFAULT_F32)
    return {"phase": "ooc_host", "n": n, "dtype": "float32",
            "operand_bytes": operand_bytes, "budget_bytes": budget,
            "bm": part.bm, "bn": part.bn, "h": part.h, "w": part.w,
            "h2d_bytes": rt.executor.last_h2d_bytes,
            "d2h_bytes": rt.executor.last_d2h_bytes,
            "cold_s": cold, "warm_s": warm, "rel_err": err,
            "tol": TOL_DEFAULT_F32, "checked_rows": int(idx.size),
            "warm_equals_cold": bool(np.array_equal(got_cold, got_warm)),
            "host_bytes_needed": need, "host_bytes_available": avail}


@jax.jit
def _ref_highest(a, b, c):
    acc = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                  precision="highest")
    return ALPHA * acc + BETA * c


def in_core_phase(n: int, seed: int) -> dict:
    """The same entry point with a budget that holds A, B and C."""
    A, B, C0 = (random_matrix((n, n), seed, s) for s in range(3))
    budget = 3 * n * n * 4
    assert is_in_core(n, n, n, budget, 4)

    def run():
        return ooc_gemm(A, B, C0, ALPHA, BETA, budget_bytes=budget,
                        backend="host")

    out, cold = timed(run)
    out, warm = timed(run)
    err = rel_err(out, _ref_highest(A, B, C0))
    check("in_core", err, TOL_DEFAULT_F32)
    return {"phase": "in_core", "n": n, "dtype": "float32",
            "cold_s": cold, "warm_s": warm, "rel_err": err,
            "tol": TOL_DEFAULT_F32}


def vmem_phase(n: int, dtype, seed: int, *, interpret: bool = False) -> dict:
    """The VMEM tier: the Pallas block-matmul kernel, compiled unless the
    caller asks for interpret mode."""
    rt = VmemOocRuntime(interpret=interpret)
    A, B, C0 = (jnp.asarray(random_matrix((n, n), seed, s))
                for s in range(3))
    A, B = A.astype(dtype), B.astype(dtype)
    budget = rt.mem_size()
    part = plan_gemm_partition(n, n, n, budget, A.dtype.itemsize)
    compiled = "tpu_custom_call" in jax.jit(
        lambda a, b, c: rt.gemm(a, b, c, ALPHA, BETA, part)
    ).lower(A, B, C0).as_text()
    if not interpret and (rt.interpret or not compiled):
        raise AssertionError("VMEM tier did not compile its Pallas kernel")

    def run():
        return np.asarray(ooc_gemm(A, B, C0, ALPHA, BETA, budget_bytes=budget,
                                   backend="vmem", runtime=rt))

    out, cold = timed(run)
    out, warm = timed(run)
    name = jnp.dtype(dtype).name
    tol = TOL_VMEM_BF16 if name == "bfloat16" else TOL_DEFAULT_F32
    err = rel_err(out, _ref_highest(A, B, C0))
    check(f"vmem {name}", err, tol)
    return {"phase": "vmem", "n": n, "dtype": name, "interpret": interpret,
            "tpu_custom_call": compiled, "cold_s": cold, "warm_s": warm,
            "rel_err": err, "tol": tol}


def mesh_phase(n: int, seed: int, devices) -> dict:
    """The MESH tier: SUMMA over a 1-D mesh of ``devices``, the ring's
    B blocks moving between chips by collective permute."""
    mesh = jax.make_mesh((len(devices),), ("model",), devices=devices,
                         axis_types=(AxisType.Auto,))
    A, B, C0 = (random_matrix((n, n), seed, s) for s in range(3))
    idx = sampled_rows(n, seed)
    rt = MeshOocRuntime(mesh)

    def run():
        # host operands: the result comes back in host memory
        out = ooc_gemm(A, B, C0, ALPHA, BETA, budget_bytes=3 * n * n * 4,
                       backend="mesh", runtime=rt)
        return out[idx]

    got, cold = timed(run)
    got, warm = timed(run)
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
              for x, s in zip((A, B, C0), rt.shardings())]
    text = rt.program().lower(*shapes, jnp.float32(ALPHA),
                              jnp.float32(BETA)).compile().as_text()
    if "collective-permute" not in text:
        raise AssertionError("SUMMA ring has no collective-permute")
    err = rel_err(got, host_ref_rows(A, B, C0, idx))
    check("mesh", err, TOL_DEFAULT_F32)
    return {"phase": "mesh", "n": n, "dtype": "float32", "devices": len(devices),
            "collective_permute": True, "cold_s": cold, "warm_s": warm,
            "rel_err": err, "tol": TOL_DEFAULT_F32,
            "checked_rows": int(idx.size)}


def emit(record: dict) -> None:
    print(json.dumps({"smoke_run": "not a benchmark", **record}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    bytes_limit = dev.memory_stats()["bytes_limit"]
    emit({"device_kind": dev.device_kind, "count": len(devices),
          "bytes_limit": bytes_limit, "compile_cache": cache})

    if args.chips == 4:
        emit(mesh_phase(32768, args.seed, devices[:4]))
    else:
        emit({**ooc_host_phase(40960, bytes_limit, args.seed),
              "peak_bytes_in_use": dev.memory_stats()["peak_bytes_in_use"]})
        emit(in_core_phase(8192, args.seed))
        emit(vmem_phase(8192, jnp.float32, args.seed))
        emit(vmem_phase(8192, jnp.bfloat16, args.seed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
