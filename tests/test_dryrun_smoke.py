"""Small-mesh dry-run smoke: the production lowering path on 8 fake devices.

Runs in a subprocess because XLA locks the host device count at first init
(the main pytest process must keep seeing 1 CPU device).
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_arch, SHAPES
from repro.distributed import make_weight_gather, tree_shardings
from repro.models import get_model
from repro.optim import AdamWConfig
from repro.training import steps as tsteps

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
results = {}
for arch in ["llama3.2-3b", "deepseek-moe-16b", "rwkv6-1.6b", "zamba2-1.2b"]:
    cfg = get_arch(arch).smoke().replace(num_heads=4, num_kv_heads=4)
    model = get_model(cfg, weight_gather=make_weight_gather(mesh))
    opt = AdamWConfig()
    state_sds = jax.eval_shape(
        lambda: tsteps.init_train_state(model, jax.random.PRNGKey(0), opt))
    axes = tsteps.train_state_logical_axes(model, True)
    ss = tree_shardings(axes, state_sds, mesh)
    B, S = 8, 32
    batch = {"inputs": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    bs = jax.tree.map(lambda s: NamedSharding(
        mesh, P("data", *([None] * (len(s.shape) - 1)))), batch)
    fn = jax.jit(tsteps.build_train_step(model, opt),
                 in_shardings=(ss, bs), out_shardings=(ss, None),
                 donate_argnums=(0,))
    compiled = fn.lower(state_sds, batch).compile()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # pre-0.5 jax returns [dict]
        cost = cost[0] if cost else {}
    results[arch] = {"flops": float(cost.get("flops", 0)),
                     "compiled": True}

    # decode path on the mesh too (zamba2/rwkv6 carry SSM state)
    cache_sds = model.cache_specs(B, 64)
    cs = tree_shardings(model.cache_logical_axes(), cache_sds, mesh)
    psds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    ps = tree_shardings(model.param_logical_axes(), psds, mesh)
    dec = jax.jit(tsteps.build_decode_step(model),
                  in_shardings=(ps, cs, NamedSharding(mesh, P("data"))),
                  out_shardings=(None, cs), donate_argnums=(1,))
    dec.lower(psds, cache_sds,
              jax.ShapeDtypeStruct((B,), jnp.int32)).compile()
    results[arch]["decode_compiled"] = True

print(json.dumps(results))
"""


@pytest.mark.slow
def test_dryrun_on_8_fake_devices():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    for arch, r in results.items():
        assert r["compiled"], arch
        assert r["decode_compiled"], arch
        assert r["flops"] > 0, arch
