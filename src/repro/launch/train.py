"""Training launcher: the real loop the examples drive.

Wires together every substrate: sharded synthetic data pipeline, AdamW +
schedule, optional gradient compression, rolling async checkpoints with
auto-resume, straggler monitoring, and mesh-sharded jit execution. Works
on the single CPU device (examples/tests) and unchanged on a real mesh —
only `mesh` and the shard index change.

CLI: python -m repro.launch.train --arch tinyllama-1.1b --steps 50 \
        --reduced --batch 8 --seq 128 [--resume] [--ckpt-dir ...]
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs import registry
from repro.configs.base import LMConfig
from repro.data import pipeline, synthetic
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim import adamw, schedule as sched
from repro.runtime import sharding
from repro.runtime.straggler import StragglerMonitor


def train_loop(cfg: LMConfig, *, steps: int = 50, batch: int = 8,
               seq: int = 128, seed: int = 0, ckpt_dir: Optional[str] = None,
               save_every: int = 20, resume: bool = False,
               log_every: int = 10, lr: float = 1e-3,
               mesh: Optional[jax.sharding.Mesh] = None,
               spiking: Optional[bool] = None) -> dict:
    mesh = mesh or make_host_mesh()
    spk = cfg.spiking.enabled if spiking is None else spiking

    # Training routes through the backend registry exactly like inference
    # — and, since the step traces under the mesh, resolution is
    # mesh-aware: capability checks run per data shard, the CSR family
    # degrades down its fallback chain instead of dropping to dense math,
    # and the attribution ("backend<-requested") records any degrade.
    if spk:
        from repro.kernels import dispatch
        resolved = " ".join(
            f"{op}={be}"
            for op, be in dispatch.resolved_backends(mesh=mesh).items())
        print(f"[train] dispatch backends (mesh-aware): {resolved}")

    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    opt_cfg = adamw.AdamWConfig(lr=lr, state_dtype=cfg.opt_state_dtype)
    opt_state = adamw.init(params, opt_cfg)

    pspecs = sharding.param_specs(cfg, params, mesh)
    p_sh = sharding.named(mesh, pspecs)
    repl = NamedSharding(mesh, P())
    o_sh = adamw.AdamWState(step=repl, mu=p_sh, nu=p_sh)
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt_state, o_sh)

    schedule_fn = functools.partial(
        sched.warmup_cosine, warmup_steps=max(2, steps // 20),
        total_steps=steps)
    step_fn = steps_mod.make_train_step(cfg, opt_cfg, schedule_fn,
                                        spiking=spk, mesh=mesh)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    mgr = CheckpointManager(ckpt_dir, save_every=save_every) \
        if ckpt_dir else None
    start_step = 0
    if mgr and resume:
        latest, restored = mgr.restore_latest((params, opt_state),
                                              (p_sh, o_sh))
        if latest is not None:
            params, opt_state = restored
            start_step = latest
            print(f"[train] resumed from step {latest}")

    n_shards = mesh.shape.get("data", 1)
    local_b = max(1, batch // n_shards)

    def make_batch(shard, step):
        return synthetic.lm_batch(seed, shard, step, local_b, seq, cfg.vocab)

    pipe = pipeline.ShardedPipeline(make_batch, n_shards, shard=0,
                                    start_step=start_step).start()
    mon = StragglerMonitor()
    losses = []
    t_start = time.time()
    it = iter(pipe)
    for step in range(start_step, steps):
        host_batch = next(it)
        dev_batch = {k: jax.numpy.asarray(v) for k, v in host_batch.items()}
        mon.step_start()
        params, opt_state, metrics = jit_step(params, opt_state, dev_batch)
        loss = float(metrics["loss"])
        report = mon.step_end()
        losses.append(loss)
        if report.get("flagged"):
            print(f"[straggler] step {step}: {report['seconds']:.2f}s "
                  f"(ema {report.get('ema', 0):.2f}s)")
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"({report['seconds']:.2f}s)")
        if mgr and mgr.should_save(step):
            mgr.save(step, (params, opt_state))
    pipe.stop()
    if mgr:
        mgr.save(steps, (params, opt_state))
        mgr.wait()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "seconds": time.time() - t_start, "params": params,
            "opt_state": opt_state,
            "straggler_flags": mon.flagged_steps}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--dense", action="store_true",
                    help="dense baseline instead of spiking")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = (registry.get_reduced(args.arch) if args.reduced
           else registry.get_config(args.arch))
    out = train_loop(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                     ckpt_dir=args.ckpt_dir, resume=args.resume, lr=args.lr,
                     spiking=None if not args.dense else False)
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"in {out['seconds']:.1f}s")


if __name__ == "__main__":
    main()
