"""Training driver: config -> mesh -> sharded train loop with
checkpoint/restart, straggler heartbeats and optional gradient
compression.

Runs on whatever devices exist (the CPU dev box trains reduced configs;
the same code on a pod trains full ones):

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
      --smoke --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, get_smoke_config
from ..data.pipeline import TokenPipeline
from ..dist.sharding import input_sharding, param_sharding
from ..models import lm
from ..runtime.checkpoint import CheckpointManager
from ..runtime.elastic import StragglerDetector
from ..train import steps as steps_mod
from .mesh import make_host_mesh, make_production_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh else make_host_mesh())
    print(f"arch={cfg.name} params={lm.param_count(cfg):,} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    step_fn = steps_mod.make_train_step(cfg, lr=args.lr,
                                        compress_grads=args.compress_grads)

    with mesh:
        params = lm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        pshard = param_sharding(jax.eval_shape(lambda: params), mesh)
        params = jax.tree.map(jax.device_put, params, pshard)
        opt = steps_mod.init_opt(cfg, params, compress_grads=args.compress_grads)
        jstep = jax.jit(step_fn, donate_argnums=(0, 1))

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ckpt and args.resume and ckpt.latest_step() is not None:
            s = ckpt.latest_step()
            params, opt, extra = ckpt.restore(s, params, opt)
            pipe.load_state_dict(extra["pipeline"])
            start = s
            print(f"resumed from step {s}")

        detector = StragglerDetector()
        losses = []
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
            t0 = time.perf_counter()
            params, opt, metrics = jstep(params, opt, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            detector.report(worker=0, step_time=dt)
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:8.4f} {dt*1e3:7.1f} ms")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, params, opt,
                          extra={"pipeline": pipe.state_dict()})
        if ckpt:
            ckpt.save(args.steps, params, opt,
                      extra={"pipeline": pipe.state_dict()})
            ckpt.wait()
        print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
        return losses


if __name__ == "__main__":
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
