"""Serving driver: prefill + batched decode with KV caches.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-27b --smoke \
      --batch 4 --prompt-len 64 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke_config
from ..models import lm
from ..train import steps as steps_mod
from .mesh import make_host_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh()
    B, S = args.batch, args.prompt_len
    key = jax.random.PRNGKey(0)

    with mesh:
        params = lm.init_params(key, cfg, jnp.float32)
        prefill = steps_mod.make_prefill_step(cfg)
        batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
        if cfg.frontend == "vision":
            batch["patches"] = jax.random.normal(key, (B, 8, cfg.d_model))
        if cfg.is_enc_dec:
            batch["enc_embeds"] = jax.random.normal(key, (B, S // 4, cfg.d_model))

        t0 = time.perf_counter()
        logits, caches = jax.jit(prefill)(params, batch)
        print(f"prefill {B}x{S}: {time.perf_counter()-t0:.2f}s")

        decode = steps_mod.make_decode_step(cfg)
        tok = jnp.argmax(logits, axis=-1)[:, None]
        toks = [tok]
        for i in range(args.gen):
            dbatch = {"tokens": tok}
            if cfg.is_enc_dec:
                dbatch["enc_embeds"] = batch["enc_embeds"]
            t0 = time.perf_counter()
            logits, caches = jax.jit(
                lambda p, c, b: decode(p, c, b, pos=S + i))(params, caches, dbatch)
            tok = jnp.argmax(logits, axis=-1)[:, None]
            toks.append(tok)
        out = jnp.concatenate(toks, axis=1)
        print("generated:", out[0].tolist())
        return out


if __name__ == "__main__":
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
