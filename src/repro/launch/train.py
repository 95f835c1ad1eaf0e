"""End-to-end training driver with checkpoint/restart and elastic resume.

Runs a real training loop on whatever devices exist (CPU here; the mesh
collapses to 1×1 for local runs, or the debug mesh under forced host
devices).  Fault-tolerance behaviors exercised:

  * ``--resume auto``: restore the latest valid checkpoint (atomic dirs),
    reshard onto the *current* mesh, seek the data pipeline to the restored
    step (no sample loss / duplication).
  * periodic async checkpointing (``--ckpt-every``).
  * deterministic seekable data (SyntheticSource) so a killed-and-restarted
    run produces bit-identical loss curves (asserted in tests).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume auto
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_arch
from repro.data import Prefetcher, SyntheticSource
from repro.distributed import make_weight_gather, tree_shardings
from repro.models import get_model
from repro.optim import AdamWConfig
from repro.training import steps as tsteps


def make_local_mesh() -> Mesh:
    """Best-effort 2-D mesh over the available devices."""
    n = len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--total-steps", type=int, default=0,
                    help="LR-schedule horizon (defaults to --steps); set it "
                         "when an interrupted run will be resumed past "
                         "--steps so the schedule is restart-invariant")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", choices=["auto", "none"], default="none")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. ~100M-param example)")
    ap.add_argument("--layers", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.d_model:
        cfg = cfg.replace(d_model=args.d_model,
                          head_dim=args.d_model // cfg.num_heads)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    cfg = cfg.replace(microbatch=args.microbatch)

    mesh = make_local_mesh()
    model = get_model(cfg, weight_gather=(
        make_weight_gather(mesh) if len(jax.devices()) > 1 else None))
    total = args.total_steps or args.steps
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=total,
                          warmup_steps=max(1, total // 10))

    state_sds = jax.eval_shape(
        lambda: tsteps.init_train_state(model, jax.random.PRNGKey(args.seed),
                                        opt_cfg))
    axes = tsteps.train_state_logical_axes(model, opt_cfg.use_master)
    state_shardings = tree_shardings(axes, state_sds, mesh)

    train_step = jax.jit(
        tsteps.build_train_step(model, opt_cfg, args.microbatch),
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,))

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if mgr and args.resume == "auto" and mgr.latest_step() is not None:
        step0 = mgr.latest_step()
        state, cursor = mgr.restore(step0, state_sds, state_shardings)
        start_step = cursor
        print(f"[resume] restored step {step0}, data cursor {cursor}")
    else:
        with mesh:
            state = jax.jit(
                lambda: tsteps.init_train_state(
                    model, jax.random.PRNGKey(args.seed), opt_cfg),
                out_shardings=state_shardings)()

    source = SyntheticSource(cfg.vocab_size, seed=args.seed)
    prefetch = Prefetcher(source, args.batch, args.seq,
                          start_step=start_step)
    n_params = sum(p.size for p in jax.tree.leaves(state["params"]))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} steps={start_step}..{args.steps}")

    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        got_step, batch = next(prefetch)
        assert got_step == step, (got_step, step)
        state, metrics = train_step(state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)", flush=True)
        if mgr and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, state, data_cursor=step + 1)
    if mgr:
        mgr.save(args.steps, state, data_cursor=args.steps, blocking=True)
        mgr.wait()
    prefetch.close()
    return {"final_loss": losses[-1] if losses else float("nan"),
            "losses": losses}


if __name__ == "__main__":
    main()
