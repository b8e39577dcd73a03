"""Training launcher: mesh + data pipeline + checkpoint/restart loop.

Production path (TPU pods): ``--mesh single|pod`` builds the 256/512-chip
mesh of launch/mesh.py and every step runs the jit'd train_step with the
full sharding contract (same code the dry-run compiles).

Smoke path (this CPU container): ``--smoke`` uses the reduced config on a
1-device mesh and actually trains — the end-to-end driver for
examples/train_lm.py.

Fault tolerance: checkpoints every --checkpoint-every steps via the atomic
CheckpointManager; on restart the latest committed step is restored and the
deterministic pipeline resumes from it (exactly-once).
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.data.pipeline import DataConfig, Pipeline, SyntheticLM
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import registry
from repro.models.common import Axes, ShapeCell
from repro.optim import adamw


def train(arch: str, *, smoke: bool = True, steps: int = 10,
          batch: int = 2, seq_len: int = 128, ckpt_dir: str | None = None,
          checkpoint_every: int = 50, lr: float = 3e-4,
          log_every: int = 10, multi_pod: bool = False,
          num_microbatches: int = 1):
    with contextlib.ExitStack() as mesh_ctx:
        if smoke:
            api = registry.get_reduced(arch)
            mesh = make_smoke_mesh()
            axes = None                  # un-meshed fast path on 1 device
        else:
            api = registry.get(arch)
            mesh = make_production_mesh(multi_pod=multi_pod)
            mesh_ctx.enter_context(jax.set_mesh(mesh))
            axes = Axes.for_mesh(mesh)
        return _train_loop(api, axes, steps=steps, batch=batch,
                           seq_len=seq_len, ckpt_dir=ckpt_dir,
                           checkpoint_every=checkpoint_every, lr=lr,
                           log_every=log_every,
                           num_microbatches=num_microbatches)


def _train_loop(api, axes, *, steps, batch, seq_len, ckpt_dir,
                checkpoint_every, lr, log_every, num_microbatches):
    cfg = api.cfg

    pipe = Pipeline(SyntheticLM(vocab=cfg.vocab, seed=0),
                    DataConfig(global_batch=batch, seq_len=seq_len))
    params = api.init_params(jax.random.key(0), axes)
    opt_state = adamw.init(params)
    opt_cfg = adamw.AdamWConfig(lr=lr)

    mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
    start_step = 0
    if mgr and mgr.latest_step() is not None:
        (state, meta) = mgr.restore_latest({"params": params,
                                            "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start_step = meta["step"]
        pipe.restore({"step": start_step, "shard": 0})
        print(f"[train] restored step {start_step}")

    step_fn = jax.jit(steps_mod.make_train_step(
        api, axes, opt_cfg, num_microbatches=num_microbatches))

    losses = []
    t_start = time.time()
    for step in range(start_step, steps):
        batch_np = pipe.next()
        loss, gnorm, params, opt_state = step_fn(params, opt_state,
                                                 batch_np)
        losses.append(float(loss))
        if (step + 1) % log_every == 0 or step == steps - 1:
            dt = time.time() - t_start
            print(f"[train] step {step + 1}/{steps} "
                  f"loss={float(loss):.4f} gnorm={float(gnorm):.2f} "
                  f"({dt / max(1, step + 1 - start_step):.2f}s/step)")
        if mgr and (step + 1) % checkpoint_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state})
    if mgr:
        mgr.save(steps, {"params": params, "opt": opt_state}, block=True)
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="full config on the production mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    args = ap.parse_args(argv)
    losses = train(args.arch, smoke=args.smoke, steps=args.steps,
                   batch=args.batch, seq_len=args.seq_len, lr=args.lr,
                   ckpt_dir=args.ckpt_dir,
                   checkpoint_every=args.checkpoint_every,
                   multi_pod=args.multi_pod)
    print(f"[train] first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
