"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads results/dryrun/<cell>.json and derives, per (arch × shape × mesh):

  compute term    = FLOPs_per_device / peak_FLOP/s
  memory term     = HBM_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / link_bw

(cost_analysis of the SPMD-partitioned module reports *per-device*
FLOPs/bytes, so the formulas divide by per-chip peaks directly — the
"/ chips" of the global-numbers formulation is already applied.)

Hardware: TPU v5e, peaks from the ``repro.device.PEAKS`` table.
Multi-pod 'pod' axis collectives ride DCN (~6.25 GB/s effective); the
per-op HLO doesn't label medium, so the collective term uses ICI bw and
the DCN adjustment is discussed qualitatively where it matters.

MODEL_FLOPS = 6·N·T (train) / 2·N·T (prefill) / 2·N·B (decode), with
N = active params for MoE; the ratio MODEL_FLOPS / HLO_FLOPs measures
how much compiled compute is "useful" (catches remat/redundancy waste —
values > 1 mean the compiler sees *less* than model flops, values ≪ 1
mean recompute/dispatch overhead dominates).
"""
from __future__ import annotations

import glob
import json
import os
from typing import Optional

from ..configs import ARCHS, get_config
from ..device import peaks
from ..models.config import shape_by_name

# the dry-run compiles for a described v5e pod
_V5E = peaks("TPU v5 lite")
PEAK_FLOPS = _V5E.bf16_flops
HBM_BW = _V5E.hbm_bytes_per_s
LINK_BW = _V5E.ici_link_bytes_per_s

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def model_flops_per_device(arch: str, shape_name: str, n_devices: int
                           ) -> float:
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    n = cfg.n_active_params()
    if shape.kind == "train":
        total = 6.0 * n * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        total = 2.0 * n * shape.global_batch * shape.seq_len
    else:  # decode: one token per sequence
        total = 2.0 * n * shape.global_batch
    return total / n_devices


def load_cells(tag: str = "") -> list[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("tag", "") != tag:
            continue
        cells.append(rec)
    return cells


def analyze(rec: dict, knobs=None) -> Optional[dict]:
    """Roofline terms for one dry-run cell.

    FLOPs / HBM / collective bytes come from the validated analytic model
    (launch/perfmodel — XLA cost_analysis undercounts scanned modules;
    see launch/calibrate for the unit-module validation).  The dry-run
    JSON supplies the per-device memory footprint and the HLO collective
    census used to sanity-check which collective kinds exist.
    """
    if not rec.get("ok") or rec.get("skipped"):
        return None
    from . import perfmodel as PM
    perf = PM.cell_perf(rec["arch"], rec["shape"], rec["mesh"], knobs)
    t_c = perf.flops / PEAK_FLOPS
    t_m = perf.hbm_bytes / HBM_BW
    t_x = perf.coll_bytes / LINK_BW
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    mf = model_flops_per_device(rec["arch"], rec["shape"],
                                rec["n_devices"])
    bound = max(t_c, t_m, t_x)
    # roofline fraction: useful-model-compute time over the bounding term
    frac = (mf / PEAK_FLOPS) / bound if bound > 0 else 0.0
    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh")},
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "dominant": dom, "model_flops": mf,
        "useful_ratio": mf / perf.flops if perf.flops else 0.0,
        "roofline_fraction": frac,
        "temp_gib": rec["memory"]["temp_bytes"] / 2**30,
        "args_gib": rec["memory"]["argument_bytes"] / 2**30,
        "coll_by_kind": perf.coll_by_kind,
        "hlo_census": rec.get("collective_bytes", {}),
        "grad_accum": rec.get("grad_accum"),
    }


def table(tag: str = "") -> list[dict]:
    return [a for a in (analyze(r) for r in load_cells(tag))
            if a is not None]


def fmt_markdown(rows: list[dict]) -> str:
    out = ["| arch | shape | mesh | compute s | memory s | collective s |"
           " dominant | MF/HLO | roofline frac | temp GiB |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
            f"| {r['t_collective_s']:.3e} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2%} "
            f"| {r['temp_gib']:.2f} |")
    return "\n".join(out)


def main():
    rows = table()
    print(fmt_markdown(rows))
    print()
    # summary: worst fractions / most collective-bound
    rows_s = sorted(rows, key=lambda r: r["roofline_fraction"])
    print("worst roofline fractions:")
    for r in rows_s[:6]:
        print(f"  {r['arch']:26s} {r['shape']:12s} {r['mesh']:6s} "
              f"{r['roofline_fraction']:.2%} dom={r['dominant']}")
    coll = sorted(rows, key=lambda r: -(r["t_collective_s"] /
                                        max(r["t_compute_s"], 1e-12)))
    print("most collective-bound (vs compute):")
    for r in coll[:6]:
        print(f"  {r['arch']:26s} {r['shape']:12s} {r['mesh']:6s} "
              f"coll/comp={r['t_collective_s']/max(r['t_compute_s'],1e-12):.2f}")


if __name__ == "__main__":
    main()
