"""Roofline analysis over the dry-run artifacts (deliverable g).

Reads the JSONs produced by ``repro.launch.dryrun`` and derives, per
(arch x shape x mesh):

    compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory term     = HLO_bytes_per_device / HBM_bw_per_chip
    collective term = collective_bytes_per_device / ICI_link_bw

(XLA cost_analysis is per-device post-SPMD, so no further division by chip
count; while-loop bodies are counted once by XLA, hence the depth-fit
extrapolation stored under "extrapolated".) Also reports MODEL_FLOPS =
6*N*D (train) / 2*N_active*D (inference) and the useful-compute ratio.

    PYTHONPATH=src python -m benchmarks.roofline [--dir DIR] [--compare tag]

The dry run compiles for the production mesh of TPU v5e chips
(launch/mesh.py), so the terms are bounded by that chip's peaks.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

from repro.configs import INPUT_SHAPES, get_config

# Per-chip peaks, keyed by jax's `Device.device_kind`. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, and
# 1,600 Gbit/s of inter-chip interconnect over 4 links (50 GB/s each).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"

DEFAULT_DIR = "benchmarks/results/dryrun"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)} (add it with its source)")
    return PEAKS[device_kind]


def model_flops_per_device(arch: str, shape_name: str, num_devices: int):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / num_devices


def load_results(dir_: str, tag: str = "") -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dir_, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if (r.get("tag") or "") != tag:
            continue
        out.append(r)
    return out


def analyze(r: Dict) -> Dict:
    # multi-pod passes run --no-fit (prove-it-lowers only): their raw
    # numbers count scan bodies once -> lower bounds, flagged in output
    peak = peaks(DRYRUN_DEVICE_KIND)
    fitted = "extrapolated" in r
    ex = r.get("extrapolated", r)
    flops = ex["flops"]
    byts = ex["bytes_accessed"]
    coll = ex["collectives"]["total_bytes"]
    t_comp = flops / peak["flops"]
    t_mem = byts / peak["hbm_bw"]
    t_coll = coll / peak["ici_bw"]
    dom = max((t_comp, "compute"), (t_mem, "memory"),
              (t_coll, "collective"))[1]
    mf = model_flops_per_device(r["arch"], r["shape"], r["num_devices"])
    return {
        "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
        "fitted": fitted,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dom,
        "model_flops": mf,
        "useful_ratio": mf / flops if flops else float("nan"),
        "hbm_gb": (r["memory"]["argument_bytes"]
                   + r["memory"]["temp_bytes"]
                   + r["memory"]["output_bytes"]) / 1e9,
    }


def run(dir_: str = DEFAULT_DIR, tag: str = "", print_csv: bool = True):
    rows = []
    for r in load_results(dir_, tag):
        a = analyze(r)
        step_time = max(a["t_compute_s"], a["t_memory_s"],
                        a["t_collective_s"])
        rows.append(
            f"roofline/{a['arch']}/{a['shape']}/{a['mesh']}"
            f"{'#' + tag if tag else ''},"
            f"{step_time * 1e6:.1f},"
            f"comp={a['t_compute_s']*1e3:.3f}ms,mem={a['t_memory_s']*1e3:.3f}ms,"
            f"coll={a['t_collective_s']*1e3:.3f}ms,dom={a['dominant']},"
            f"useful={a['useful_ratio']:.2f},hbm={a['hbm_gb']:.1f}GB"
            + ("" if a["fitted"] else ",NOFIT(lower-bound)"))
    if print_csv:
        for row in rows:
            print(row)
    return rows


def compare(dir_: str, tag_a: str, tag_b: str):
    """Before/after table for the perf hillclimb (§Perf)."""
    ra = {(r["arch"], r["shape"], r["mesh"]): analyze(r)
          for r in load_results(dir_, tag_a)}
    rb = {(r["arch"], r["shape"], r["mesh"]): analyze(r)
          for r in load_results(dir_, tag_b)}
    rows = []
    for key in sorted(set(ra) & set(rb)):
        a, b = ra[key], rb[key]
        dom = a["dominant"]
        ta = a[f"t_{dom}_s"]
        tb = b[f"t_{dom}_s"]
        rows.append(f"perf/{'/'.join(key)},{tb*1e6:.1f},"
                    f"dom={dom},before={ta*1e3:.3f}ms,after={tb*1e3:.3f}ms,"
                    f"delta={100*(tb-ta)/ta:+.1f}%")
    for row in rows:
        print(row)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=DEFAULT_DIR)
    ap.add_argument("--tag", default="")
    ap.add_argument("--compare", nargs=2, metavar=("TAG_A", "TAG_B"))
    args = ap.parse_args()
    if args.compare:
        compare(args.dir, *args.compare)
    else:
        run(args.dir, args.tag)
