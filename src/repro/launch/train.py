"""Distributed training launcher.

The production entry point tying the pieces together: build the mesh,
shard parameters/optimizer with the launch/sharding.py policy, run the
microbatched+remat train step under the chosen PerfPolicy, journal the
data order, and write SI-consistent async checkpoints — with restart
(``--resume``) picking up from the last checkpoint + WAL tail exactly
(the recovery path is exercised end-to-end by examples/train_lm.py).

On this CPU container it runs reduced configs for real; on a TPU slice the
same file is the per-host program (jax.distributed.initialize handles the
multi-host runtime; the mesh spans all devices).

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
        --reduced --steps 20 --mesh host
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np
from jax.sharding import AxisType

from repro import policy as perf
from repro.checkpoint import snapshot
from repro.configs import ARCH_IDS, get_arch, reduced
from repro.data.pipeline import DataConfig, make_batch
from repro.launch import sharding as shp
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.train import optimizer as opt
from repro.train.trainstep import make_train_step


def make_mesh(kind: str):
    if kind == "host":            # whatever this host offers (CPU: 1)
        n = len(jax.devices())
        return jax.make_mesh((n, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    return make_production_mesh(multi_pod=(kind == "multipod"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--micro", type=int, default=2)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "pod", "multipod"])
    ap.add_argument("--policy", default="baseline",
                    choices=list(perf.POLICIES))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    perf.set_policy(args.policy)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build(cfg)
    mesh = make_mesh(args.mesh)
    ocfg = opt.AdamWConfig(total_steps=args.steps)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)

    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        pspec = shp.param_pspecs(params, mesh)
        shardings = shp.to_shardings(pspec, mesh)
        params = jax.tree.map(jax.device_put, params, shardings)
        ostate = opt.init(params)
        start = 0
        if args.resume and args.ckpt_dir and os.path.exists(
                os.path.join(args.ckpt_dir, "manifest.json")):
            params, ostate, meta = snapshot.restore(
                args.ckpt_dir, params, ostate)
            start = meta["step"]
            print(f"[train] resumed from step {start}")

        step_fn = jax.jit(
            make_train_step(model, ocfg, n_microbatches=args.micro,
                            grad_specs=pspec),
            in_shardings=(shardings, shp.to_shardings(
                shp.opt_pspecs(pspec), mesh), None),
            donate_argnums=(0, 1))

        ckpt_thread = None
        t0 = time.time()
        for i in range(start, args.steps):
            batch = make_batch(dcfg, i)
            params, ostate, metrics = step_fn(params, ostate, batch)
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                if ckpt_thread is not None:
                    ckpt_thread.join()
                ckpt_thread = snapshot.save_async(
                    args.ckpt_dir, params, ostate, step=i + 1)
            if (i + 1) % 10 == 0 or i + 1 == args.steps:
                dt = (time.time() - t0) / max(1, i + 1 - start)
                print(f"[train] step {i + 1:5d} "
                      f"loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics.get('grad_norm', np.nan)):.3f} "
                      f"{dt * 1e3:.0f} ms/step")
        if ckpt_thread is not None:
            ckpt_thread.join()
    print("[train] done")


if __name__ == "__main__":
    main()
