"""Quickstart: out-of-core GEMM through the three TPU memory tiers.

Runs on CPU (vmem backend in interpret mode; mesh backend needs >1 device —
skipped gracefully).  ~30 s.
"""
import numpy as np

from repro.core import (VmemOocRuntime, build_gemm_schedule, gpu_like,
                        ooc_gemm, plan_gemm_partition, schedule_stats,
                        simulate, tpu_v5e_vmem, validate_schedule)

rng = np.random.default_rng(0)
M, N, K = 768, 640, 512
A = rng.standard_normal((M, K)).astype(np.float32)
B = rng.standard_normal((K, N)).astype(np.float32)
C = rng.standard_normal((M, N)).astype(np.float32)
ref = 1.5 * A @ B + 0.5 * C
budget = (A.nbytes + B.nbytes + C.nbytes) // 5   # force out-of-core

# 1. plan: how does the hclMatrixPartitioner split this under the budget?
part = plan_gemm_partition(M, N, K, budget, 4)
print(f"partition: {part.h}x{part.w} blocks of {part.bm}x{part.bn} "
      f"(working set {part.working_set_bytes()/1e6:.2f} MB "
      f"<= budget {budget/1e6:.2f} MB)")

# 2. schedule: the paper's Fig.2 event program, generated + validated
sched = build_gemm_schedule(part, nstreams=2, nbuf=2)
validate_schedule(sched)
print(f"schedule: {schedule_stats(sched)}")

# 3. execute on the host-streaming backend
out = ooc_gemm(A, B, C, 1.5, 0.5, budget_bytes=budget, backend="host")
print(f"host backend max err: {np.abs(out - ref).max():.2e}")

# 4. execute through the Pallas VMEM kernel (interpret mode on CPU)
out_v = ooc_gemm(A, B, C, 1.5, 0.5, budget_bytes=budget, backend="vmem",
                 runtime=VmemOocRuntime(interpret=True))
print(f"vmem backend max err: {np.abs(np.asarray(out_v) - ref).max():.2e}")

# 5. what would this schedule do on real hardware?  (engine model)
for hw in (gpu_like(), tpu_v5e_vmem()):
    res = simulate(sched, hw)
    print(f"{hw.name}: {res.effective_flops/1e9:.1f} GFLOP/s effective, "
          f"exec util {res.utilization('exec'):.2f}")
print("quickstart OK")
