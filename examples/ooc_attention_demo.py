"""OOC attention demo: the MMOOC pipeline reused for a KV cache.

A decode-step query attends over a cache larger than the (simulated) fast
tier; KV blocks stream through the same double-buffered schedule as the
GEMM, with an online-softmax carry instead of the beta-accumulate.
"""
import numpy as np

from repro.core import (build_attention_schedule, plan_attention_partition,
                        schedule_stats, simulate, tpu_v5e_vmem,
                        validate_schedule)
from repro.core.ooc_attention import ooc_attention
from repro.kernels import ops, ref
import jax.numpy as jnp

rng = np.random.default_rng(0)
H, hkv, d, S = 32, 8, 128, 8192
q = rng.standard_normal((H, d)).astype(np.float32)
k = rng.standard_normal((S, hkv, d)).astype(np.float32)
v = rng.standard_normal((S, hkv, d)).astype(np.float32)
budget = S * hkv * d * 4 // 4     # cache is 4x the fast tier

part = plan_attention_partition(S, hkv, d, budget)
print(f"KV cache split into {part.nblocks} blocks of {part.bs} positions")

sched = build_attention_schedule(part, hkv, d, H)
validate_schedule(sched)
print(f"schedule: {schedule_stats(sched)}")

out = ooc_attention(q, k, v, budget_bytes=budget)
expect = ref.decode_attention_ref(
    jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
    jnp.asarray([S]))[0]
print(f"engine max err vs oracle: "
      f"{np.abs(np.asarray(out) - np.asarray(expect)).max():.2e}")

# the same computation through the Pallas kernel (interpret mode on CPU),
# which takes the cache head-major: (B, Hkv, S, d)
out_k = ops.flash_decode_attention(
    jnp.asarray(q)[None], jnp.asarray(k).transpose(1, 0, 2)[None],
    jnp.asarray(v).transpose(1, 0, 2)[None],
    jnp.asarray([S]), block_s=512, interpret=True)[0]
print(f"pallas max err vs oracle: "
      f"{np.abs(np.asarray(out_k) - np.asarray(expect)).max():.2e}")

res = simulate(sched, tpu_v5e_vmem())
print(f"on v5e VMEM tier: {res.makespan*1e6:.1f} us/token, "
      f"DMA util {res.utilization('in'):.2f} (memory-bound, as decode is)")
print("ooc_attention_demo OK")
