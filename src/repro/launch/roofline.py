"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads the per-cell JSON written by ``launch/dryrun.py`` and derives the three
roofline terms per (arch × shape × mesh):

    compute term    = HLO_FLOPs_per_device      / peak_FLOPs_per_chip
    memory term     = HLO_bytes_per_device      / HBM_bandwidth_per_chip
    collective term = collective_bytes_per_dev  / ICI_link_bandwidth

``cost_analysis()`` and the parsed HLO are the *per-device* program (post-SPMD), so
dividing by per-chip peaks is the per-chip time directly — equivalent to the global
formulation ``global_quantity / (chips × peak)`` since global = per_device × chips.

Also reports MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) per device-step, the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs (catches remat/redundancy waste), the
dominant term, and a one-line "what would move it" note.

Usage:
    PYTHONPATH=src python -m repro.launch.roofline --in results/dryrun [--md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import SHAPES, get
from repro.launch.peaks import V5E, peaks

# Projections target one TPU v5e chip; its peaks come from the one table.
_CHIP = peaks(V5E)
PEAK_BF16 = _CHIP.bf16_flops        # FLOP/s
PEAK_INT8 = _CHIP.int8_ops          # OP/s (MXU int8 runs at 2x bf16)
HBM_BW = _CHIP.hbm_bw               # B/s
ICI_BW = _CHIP.ici_bw / 4           # B/s per link (4 links per v5e chip)


MESH_DEVICES = {"pod16x16": 256, "pod2x16x16": 512}


def model_flops_per_step(arch: str, shape_name: str, n_devices: int) -> float:
    """6·N·D (training) or 2·N·D (inference fwd) useful model FLOPs per device-step."""
    cfg = get(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n * shape.global_batch
    return total / n_devices   # per device


def terms(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    st = rec.get("static")
    if st:
        # Trip-count-aware figures (launch/hlo_static.py). int8 dots run the MXU at
        # 2× bf16 peak, so they contribute at PEAK_INT8.
        flops = st["flops_fp"] + st["flops_int8"]
        t_c = st["flops_fp"] / PEAK_BF16 + st["flops_int8"] / PEAK_INT8
        t_m = st["hbm_bytes"] / HBM_BW
        t_x = st["collective_bytes"] / ICI_BW
    else:  # legacy records (cost_analysis counts while bodies once — underestimates)
        flops = rec["cost"]["flops"]
        t_c = flops / PEAK_BF16
        t_m = rec["cost"]["bytes"] / HBM_BW
        t_x = rec["collective_bytes"] / ICI_BW
    dominant = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    n_dev = MESH_DEVICES.get(rec.get("mesh", ""), rec["dp"] * rec["tp"])
    mf = model_flops_per_step(rec["arch"], rec["shape"], n_dev)
    bound = max(t_c, t_m, t_x)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / flops if flops > 0 else 0.0,
        # Fraction of roofline: useful model FLOP time over the bound set by the
        # dominant term — the score we hillclimb.
        "roofline_fraction": (mf / PEAK_BF16) / bound if bound > 0 else 0.0,
    }


SUGGEST = {
    "compute": "cut non-model FLOPs (remat policy, fp32->bf16 epilogues) or move the "
               "GEMMs to the int8 MXU path (2x peak)",
    "memory": "fuse quantize-dequant chains, shrink activation dtypes, or serve "
              "prepared int8/int4 weights (2-4x fewer weight bytes)",
    "collective": "reshard to cut all-gathers (stronger TP tier / EP), overlap "
                  "collectives with compute, or compress the payload (int8 grads)",
}


def load(results_dir: str) -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def format_table(recs: List[Dict], md: bool = False) -> str:
    rows = []
    header = ("arch", "shape", "mesh", "quant", "tier", "GiB/dev", "compute_s",
              "memory_s", "collect_s", "dominant", "useful%", "roofline%")
    for rec in recs:
        t = terms(rec)
        if t is None:
            rows.append((rec["arch"], rec["shape"], rec.get("mesh", "-"),
                         rec.get("quant", "-"), rec.get("status"),
                         rec.get("reason", rec.get("error", ""))[:40],
                         "-", "-", "-", "-", "-", "-"))
            continue
        rows.append((
            rec["arch"], rec["shape"], rec["mesh"], rec["quant"], rec["tier"],
            f"{rec['per_device_bytes'] / 2**30:.2f}",
            f"{t['compute_s']:.4f}", f"{t['memory_s']:.4f}",
            f"{t['collective_s']:.4f}", t["dominant"],
            f"{100 * t['useful_ratio']:.0f}", f"{100 * t['roofline_fraction']:.1f}",
        ))
    widths = [max(len(str(r[i])) for r in [header] + rows) for i in range(len(header))]
    sep = " | " if md else "  "
    lines = [sep.join(str(h).ljust(w) for h, w in zip(header, widths))]
    if md:
        lines.insert(0, "| " + lines[0] + " |")
        lines[0] = lines[0]
        lines = ["| " + sep.join(str(h).ljust(w) for h, w in zip(header, widths)) + " |",
                 "|" + "|".join("-" * (w + 2) for w in widths) + "|"]
        for r in rows:
            lines.append("| " + sep.join(str(c).ljust(w) for c, w in zip(r, widths)) + " |")
    else:
        for r in rows:
            lines.append(sep.join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="results", default="results/dryrun")
    ap.add_argument("--md", action="store_true", help="markdown table")
    ap.add_argument("--suggest", action="store_true", help="print per-cell next move")
    args = ap.parse_args()
    recs = load(args.results)
    print(format_table(recs, md=args.md))
    if args.suggest:
        print()
        for rec in recs:
            t = terms(rec)
            if t:
                print(f"{rec['arch']} {rec['shape']} [{t['dominant']}-bound] -> "
                      f"{SUGGEST[t['dominant']]}")


if __name__ == "__main__":
    main()
