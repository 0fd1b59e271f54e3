"""Roofline analysis from the dry-run artifacts (deliverable g).

Per (arch x shape x mesh) cell, from results/dryrun/*.json:

  compute term    = HLO_FLOPs(per-device) / peak_FLOP/s
  memory term     = HLO_bytes(per-device) / HBM_bw
  collective term = collective_bytes(per-device) / link_bw

Hardware: TPU v5e-like — 197 TFLOP/s bf16/chip, 819 GB/s HBM,
~50 GB/s/link ICI.  XLA's cost_analysis on the partitioned module is
already per-device; collective bytes parsed from the optimized HLO are
per-device payloads.

MODEL_FLOPS = 6*N*D (train) / 2*N*D (serve) with N_active for MoE;
the ratio MODEL_FLOPS / (HLO_FLOPs x chips) flags remat/redundancy waste
(XLA counts dots as MACs on CPU, so a ratio near 2.0 is "clean").
"""
from __future__ import annotations

import glob
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def model_flops(arch: str, shape: str) -> float:
    """Analytic useful-FLOPs for one step of the cell (whole job)."""
    from repro.configs import get_config
    from repro.configs.registry import SHAPES
    from repro.models.lm import param_count

    if arch == "nshedb":
        # modmul count model (Table 3): per block, (eq_levels + 1) ct-muls
        # x 3 limb-products x k^2-ish keyswitch + rotations; count the
        # dominant barrett muls: per ct-op ~ (3k + k^2) * n lane-muls.
        from repro.configs.nshedb import CONFIG, SHAPES as NSH
        k, n = CONFIG.k, CONFIG.n
        nblocks = NSH[shape]["nblocks"]
        ct_ops = CONFIG.eq_levels + 1 + CONFIG.rot_steps
        lane_muls = nblocks * ct_ops * (3 * k + k * k) * n
        return lane_muls * 2.0          # mul+add per lane FMA-equivalent

    cfg = get_config(arch)
    info = SHAPES[shape]
    tokens = info["seq"] * info["batch"] if info["kind"] != "decode" \
        else info["batch"]
    n_active = cfg.active_param_count() if cfg.is_moe else param_count(cfg)
    per_tok = 6 * n_active if info["kind"] == "train" else 2 * n_active
    return float(per_tok) * tokens


def load_cells() -> list[dict]:
    out = []
    for fn in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(fn) as f:
            out.append(json.load(f))
    return out


def analyze(cell: dict) -> dict | None:
    if cell.get("status") != "ok":
        return {"arch": cell["arch"], "shape": cell["shape"],
                "mesh": cell["mesh"], "status": cell.get("error", "fail")[:60]}
    chips = 1
    for d in cell["mesh_shape"]:
        chips *= d
    t_comp = cell["flops"] / PEAK_FLOPS
    t_mem = cell["hlo_bytes"] / HBM_BW
    t_coll = cell["collective_total"] / ICI_BW
    dom = max(("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
              key=lambda kv: kv[1])[0]
    mf = model_flops(cell["arch"], cell["shape"])
    # analytic useful-compute time per chip (XLA's cost_analysis counts a
    # while-loop body ONCE, so scanned-layer models under-report; this
    # column is the loop-corrected term the §Perf discussion uses).
    t_model = mf / (chips * PEAK_FLOPS)
    ratio = mf / (cell["flops"] * chips) if cell["flops"] > 0 else 0.0
    bound = max(t_comp, t_mem, t_coll, t_model)
    return {
        "arch": cell["arch"], "shape": cell["shape"], "mesh": cell["mesh"],
        "t_compute_s": f"{t_comp:.2e}", "t_memory_s": f"{t_mem:.2e}",
        "t_collective_s": f"{t_coll:.2e}", "t_model_s": f"{t_model:.2e}",
        "dominant": dom,
        "roofline_frac": round(t_model / bound, 3) if bound else 0.0,
        "model/hlo_flops": round(ratio, 2),
        "peak_GiB": round(cell["peak_bytes"] / 2**30, 2),
        "fits_16GiB": cell["peak_bytes"] < 16 * 2**30,
    }


def main(quick: bool = False) -> str:
    from .common import save_json, table
    cells = load_cells()
    rows = [analyze(c) for c in cells]
    rows = [r for r in rows if r]
    rows.sort(key=lambda r: (r.get("mesh", ""), r.get("arch", ""), r.get("shape", "")))
    save_json("roofline.json", rows)
    singles = [r for r in rows if r.get("mesh") == "single"]
    multis = [r for r in rows if r.get("mesh") == "multi"]
    out = table(singles, "Roofline — single pod (16x16 = 256 chips)")
    out += "\n" + table(multis, "Roofline — multi pod (2x16x16 = 512 chips)")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())
