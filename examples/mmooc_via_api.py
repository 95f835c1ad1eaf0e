"""MMOOC written against the unified libhclooc-style API (paper Fig. 2).

This file is the LOC *numerator* for claim C4: compare with the three
backend-specific implementations in benchmarks/direct_impls.py.  The same
code runs on every memory tier by changing the device tuple — the paper's
{"GPU"| "PHI"| "FPGA"} becomes {"HBM"| "VMEM"| "MESH"}.
"""
import sys

import numpy as np

from repro.core.api import (hclDeviceFactory, hclMatrixPartitioner,
                            hclRuntimeFactory)


def mmooc(A, B, C, alpha, beta, device_name="HBM", device_id=0,
          mem_bytes=None, mesh=None, **runtime_kw):
    d = hclDeviceFactory.create(device_name, device_id, mem_bytes)
    r = hclRuntimeFactory.create(d, mesh, **runtime_kw)
    part = hclMatrixPartitioner(A.shape[0], B.shape[1], A.shape[1],
                                d.mem_size(), A.dtype.itemsize)
    return r.gemm(A, B, C, alpha, beta, part)


if __name__ == "__main__":
    rng = np.random.default_rng(0)
    M, N, K = 768, 512, 384
    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    C = rng.standard_normal((M, N)).astype(np.float32)
    budget = (A.nbytes + B.nbytes + C.nbytes) // 5   # force out-of-core
    # the VMEM tier's Pallas kernel runs interpreted off a TPU
    for dev, kw in (("HBM", {}), ("VMEM", {"interpret": True})):
        out = mmooc(A, B, C, 1.5, 0.5, dev, mem_bytes=budget, **kw)
        err = np.abs(np.asarray(out) - (1.5 * A @ B + 0.5 * C)).max()
        print(f"{dev}: max err {err:.2e}")
        assert err < 1e-2
    print("mmooc_via_api OK")
