"""Elastic rescale: a checkpoint written under one mesh restores onto a
different device count/topology (the fault-tolerance contract for node
loss / cluster resize).  Subprocess-per-mesh because XLA pins the host
device count at first init.

Each subprocess reports a parameter checksum AND the model loss on a
deterministic batch, so rescales are checked for *loss parity* — the
restored model must behave identically, not merely carry the same bytes.
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.distributed import tree_shardings
from repro.models import get_model
from repro.optim import AdamWConfig
from repro.training import steps as tsteps

ndev, shape, mode, ckpt = (int(sys.argv[1]), sys.argv[2], sys.argv[3],
                           sys.argv[4])
rows, cols = (int(x) for x in shape.split("x"))
assert rows * cols == ndev, (shape, ndev)
mesh = jax.make_mesh((rows, cols), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = get_arch("stablelm-1.6b").smoke().replace(num_heads=4, num_kv_heads=4)
model = get_model(cfg)
opt = AdamWConfig()
sds = jax.eval_shape(
    lambda: tsteps.init_train_state(model, jax.random.PRNGKey(0), opt))
shardings = tree_shardings(
    tsteps.train_state_logical_axes(model, True), sds, mesh)
mgr = CheckpointManager(ckpt)

batch = {"inputs": jnp.arange(8 * 16, dtype=jnp.int32).reshape(8, 16) % 64,
         "labels": jnp.ones((8, 16), jnp.int32)}

def eval_loss(state):
    loss_fn = jax.jit(tsteps.build_loss_fn(model))
    return float(loss_fn(state["params"], batch))

if mode == "save":
    with mesh:
        state = jax.jit(lambda: tsteps.init_train_state(
            model, jax.random.PRNGKey(0), opt),
            out_shardings=shardings)()
    # one real step so the state is non-trivial
    step = jax.jit(tsteps.build_train_step(model, opt),
                   in_shardings=(shardings, None),
                   out_shardings=(shardings, None))
    tb = {"inputs": jnp.zeros((8, 16), jnp.int32),
          "labels": jnp.zeros((8, 16), jnp.int32)}
    state, _ = step(state, tb)
    mgr.save(1, state, data_cursor=1, blocking=True)
    ck = float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                   for x in jax.tree.leaves(state["params"])))
    print(json.dumps({"checksum": ck, "loss": eval_loss(state)}))
else:
    state, cursor = mgr.restore(1, sds, shardings)
    assert cursor == 1
    # verify placement matches THIS mesh and values survived
    lead = jax.tree.leaves(state["params"])[0]
    assert len(lead.sharding.mesh.devices.flatten()) == ndev
    assert lead.sharding.mesh.devices.shape == (rows, cols)
    ck = float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                   for x in jax.tree.leaves(state["params"])))
    print(json.dumps({"checksum": ck, "loss": eval_loss(state)}))
"""


def _runner(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    ck = str(tmp_path / "ck")

    def run(ndev, shape, mode):
        out = subprocess.run(
            [sys.executable, "-c", SCRIPT, str(ndev), shape, mode, ck],
            env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    return run


def _assert_parity(saved, restored, what):
    assert abs(saved["checksum"] - restored["checksum"]) \
        <= 1e-5 * abs(saved["checksum"]), what
    assert abs(saved["loss"] - restored["loss"]) \
        <= 1e-4 * max(abs(saved["loss"]), 1e-8), what


@pytest.mark.slow
def test_checkpoint_restores_on_different_mesh(tmp_path):
    run = _runner(tmp_path)
    saved = run(8, "4x2", "save")
    restored = run(4, "2x2", "restore")  # "half the cluster died"
    _assert_parity(saved, restored, "8 -> 4 devices")
    grown = run(8, "4x2", "restore")     # scale back up
    _assert_parity(saved, grown, "4 -> 8 devices")


@pytest.mark.slow
def test_checkpoint_rescale_shrink_and_repartition(tmp_path):
    """The coverage the single test above missed: a shrink that halves a
    4-device mesh (4 -> 2), and a restore onto the SAME device count with a
    changed partition config (2x2 data-parallel-heavy -> 4x1 pure
    data-parallel) — both must preserve the deterministic-batch loss."""
    run = _runner(tmp_path)
    saved = run(4, "2x2", "save")
    shrunk = run(2, "1x2", "restore")    # 4 -> 2 devices
    _assert_parity(saved, shrunk, "4 -> 2 devices")
    repart = run(4, "4x1", "restore")    # same devices, new partitioning
    _assert_parity(saved, repart, "2x2 -> 4x1 repartition")
