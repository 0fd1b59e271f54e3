import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell on 512 virtual devices and record memory/cost/collective analysis.

MUST be executed as its own process (the XLA_FLAGS line above runs
before any other import, including jax): `python -m repro.launch.dryrun`.

Per cell we persist a JSON record under results/dryrun/ with:
  bytes per device (memory_analysis), HLO flops/bytes (cost_analysis),
  collective bytes by op kind (parsed from the optimized HLO), wall
  compile time — everything benchmarks/roofline.py consumes.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --arch nshedb --shape scan_33m
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _op_bytes(shape_str: str) -> int:
    """Sum byte sizes of every tensor literal in an HLO result/operand."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in optimized HLO."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+(\S+)\(", s)
        if not m:
            continue
        op = m.group(2).rstrip("(").split(".")[0]
        for kind in _COLLECTIVES:
            if op == kind or op == kind + "-start":
                out[kind] += _op_bytes(m.group(1))
    return out


def _mesh(kind: str):
    from .mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multi"))


# ---------------------------------------------------------------------------
# Cell builders: return (fn, args_specs, in_shardings) ready to lower.
# ---------------------------------------------------------------------------

def build_lm_cell(arch: str, shape: str, mesh):
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import get_config, input_specs
    from ..dist.sharding import cache_sharding, input_sharding, param_sharding
    from ..models import lm
    from ..train import steps as steps_mod
    from ..train.optim import adamw_init

    cfg = get_config(arch)
    specs = input_specs(cfg, shape, dtype=jnp.bfloat16)
    kind = specs["kind"]

    pshapes = jax.eval_shape(
        lambda k: lm.init_params(k, cfg, jnp.bfloat16),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    pshard = param_sharding(pshapes, mesh)
    batch_specs = {k: v for k, v in specs.items()
                   if k in ("tokens", "labels", "patches", "enc_embeds")}
    bshard = input_sharding(batch_specs, mesh)

    if kind == "train":
        oshapes = jax.eval_shape(adamw_init, pshapes)
        oshard = {"adam": param_sharding(oshapes, mesh)}
        oshapes = {"adam": oshapes}
        step = steps_mod.make_train_step(cfg)
        args = (pshapes, oshapes, batch_specs)
        shardings = (pshard, oshard, bshard)
        return step, args, shardings, (pshard, oshard, None)

    B = specs["batch"]

    def _logit_shard(shape):
        names = mesh.axis_names
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        ba = tuple(a for a in ("pod", "data") if a in names)
        nb = 1
        for a in ba:
            nb *= sizes[a]
        ba = (ba if len(ba) > 1 else (ba[0] if ba else None)) \
            if nb and shape[0] % max(nb, 1) == 0 else None
        v_ax = "model" if shape[-1] % sizes.get("model", 1) == 0 else None
        return NamedSharding(mesh, P(ba, v_ax))

    if kind == "prefill":
        step = steps_mod.make_prefill_step(cfg)
        args = (pshapes, batch_specs)
        # The returned KV caches are built inside the step; without
        # explicit out_shardings GSPMD under-shards them (perf iteration
        # #2: qwen2 prefill output was 20 GiB/device batch-only-sharded).
        out_shapes = jax.eval_shape(step, pshapes, batch_specs)
        out_sh = (_logit_shard(out_shapes[0].shape),
                  cache_sharding(out_shapes[1], mesh, B))
        return step, args, (pshard, bshard), out_sh

    # decode
    ctx = specs["cache_len"]
    cshapes = jax.eval_shape(
        functools.partial(lm.make_cache, cfg, B, ctx, jnp.bfloat16))
    cshard = cache_sharding(cshapes, mesh, B)
    base = steps_mod.make_decode_step(cfg)
    step = functools.partial(base, pos=ctx)
    args = (pshapes, cshapes, batch_specs)
    out_shapes = jax.eval_shape(step, pshapes, cshapes, batch_specs)
    out_sh = (_logit_shard(out_shapes[0].shape),
              cache_sharding(out_shapes[1], mesh, B))
    return step, args, (pshard, cshard, bshard), out_sh


def build_nshedb_cell(shape: str, mesh):
    import functools

    from jax.sharding import NamedSharding

    from ..configs.nshedb import CONFIG, SHAPES
    from . import nshedb_step as Q

    cfg = CONFIG
    cell = SHAPES[shape]
    nblocks = cell["nblocks"]
    specs = Q.input_specs(cfg, nblocks)
    shard = Q.shardings(mesh, cfg, nblocks)
    fn = functools.partial(Q.query_step, eq_levels=cfg.eq_levels,
                           rot_steps=cell.get("rot_steps", cfg.rot_steps),
                           ks_mode=cell.get("ks_mode"))
    names = list(specs)
    step = lambda *a: fn(**dict(zip(names, a)))
    args = tuple(specs[n] for n in names)
    shardings = tuple(shard[n] for n in names)
    return step, args, (shardings,)


# ---------------------------------------------------------------------------
# Runner.
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: str, mesh_kind: str, *, save: bool = True) -> dict:
    mesh = _mesh(mesh_kind)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "mesh_shape": list(mesh.devices.shape), "status": "ok"}
    t0 = time.time()
    try:
        out_sh = None
        if arch == "nshedb":
            step, args, shardings = build_nshedb_cell(shape, mesh)
            flat_shardings = shardings[0]
        else:
            step, args, shardings, out_sh = build_lm_cell(arch, shape, mesh)
            flat_shardings = shardings
        with mesh:
            kw = {"out_shardings": out_sh} if out_sh is not None else {}
            jitted = jax.jit(step, in_shardings=flat_shardings, **kw)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update(
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            flops=float(cost.get("flops", -1.0)),
            hlo_bytes=float(cost.get("bytes accessed", -1.0)),
            argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
            output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
            temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
            peak_bytes=int(getattr(mem, "peak_memory_in_bytes", 0) or
                           (getattr(mem, "argument_size_in_bytes", 0)
                            + getattr(mem, "temp_size_in_bytes", 0))),
            collective_bytes=coll,
            collective_total=sum(coll.values()),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    rec["wall_s"] = round(time.time() - t0, 1)
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        fn = f"{arch}__{shape}__{mesh_kind}.json".replace("/", "_")
        with open(os.path.join(RESULTS_DIR, fn), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from ..configs import ARCHS, shape_cells
    from ..configs.nshedb import SHAPES as NSHAPES

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCHS:
            for shape, skip in shape_cells(arch):
                if skip is None:
                    cells.append((arch, shape))
        for shape in NSHAPES:
            cells.append(("nshedb", shape))
    else:
        assert args.arch and args.shape, "--arch + --shape or --all"
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        for mk in meshes:
            fn = os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mk}.json")
            if args.skip_existing and os.path.exists(fn):
                with open(fn) as f:
                    if json.load(f).get("status") == "ok":
                        print(f"SKIP {arch} {shape} {mk} (cached)")
                        continue
            rec = run_cell(arch, shape, mk)
            msg = (f"{rec['status'].upper():4s} {arch:20s} {shape:12s} {mk:6s} "
                   f"compile={rec.get('compile_s', '-')}s")
            if rec["status"] == "ok":
                msg += (f" flops={rec['flops']:.3g}"
                        f" coll={rec['collective_total']:.3g}B"
                        f" peak={rec['peak_bytes']/2**30:.2f}GiB/dev")
            else:
                msg += f" err={rec['error'][:120]}"
            print(msg, flush=True)


if __name__ == "__main__":
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
