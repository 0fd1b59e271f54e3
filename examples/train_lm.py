"""Train a ~100M-parameter LM for a few hundred steps on the host
(deliverable b: end-to-end driver), with checkpoint/resume.

The config is a scaled-down starcoder2 (same code path as the 3B/72B
configs; the launcher shards it the same way on a pod).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import TokenPipeline
from repro.models import lm
from repro.models.config import ModelConfig
from repro.runtime.checkpoint import CheckpointManager
from repro.train import steps as steps_mod


def config_100m() -> ModelConfig:
    return ModelConfig(
        name="starcoder2-100m", d_model=512, n_layers=8, vocab=32768,
        n_heads=8, n_kv_heads=2, head_dim=64,
        pattern=("attn",), d_ff=2048, mlp_gated=False,
        tie_embeddings=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = config_100m()
    print(f"{cfg.name}: {lm.param_count(cfg)/1e6:.1f}M params")
    params = lm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    opt = steps_mod.init_opt(cfg, params)
    step = jax.jit(steps_mod.make_train_step(cfg, lr=3e-4),
                   donate_argnums=(0, 1))
    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)

    losses = []
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
        if i % 25 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {losses[-1]:.4f}")
        if (i + 1) % 100 == 0:
            ckpt.save(i + 1, params, opt, extra={"pipeline": pipe.state_dict()})
    ckpt.wait()
    first, last = np.mean(losses[:20]), np.mean(losses[-20:])
    print(f"\nloss: {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
