"""Roofline report generator: reads results/dryrun/*.json and emits the
three-term table (compute / memory / collective, seconds per step per
device) with the dominant bottleneck per (arch × shape × mesh).

Hardware constants come from the ``device_kind``-keyed peak table
(:mod:`repro.kernels.peaks`); the dry run models the v5e production mesh.
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.kernels.peaks import TARGET_KIND, chip_peaks

_PEAKS = chip_peaks(TARGET_KIND)
PEAK_FLOPS = _PEAKS.flops
HBM_BW = _PEAKS.hbm_bytes_per_s
ICI_BW = _PEAKS.ici_bytes_per_s

RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"


def load_cells(include_act_variants: bool = False):
    cells = []
    for f in sorted(RESULTS.glob("*.json")):
        r = json.loads(f.read_text())
        if r.get("act_mode") and not include_act_variants:
            continue  # act-mode variants are §Perf experiments, not baseline
        cells.append(r)
    return cells


def roofline_row(rec):
    """Three terms in seconds/step/device + bottleneck + model/hlo ratio."""
    if rec["status"] != "ok":
        return {"arch": rec["arch"], "shape": rec["shape"],
                "mesh": rec["mesh"], "status": rec["status"],
                "reason": rec.get("reason", "")}
    h = rec["hlo"]
    # CPU lowering promotes most bf16 math to f32: halve byte terms to model
    # the TPU bf16 layout (documented caveat; flops are dtype-agnostic).
    f32_factor = 0.5
    t_compute = h["dot_flops_per_device"] / PEAK_FLOPS
    t_memory = h["hbm_bytes_per_device"] * f32_factor / HBM_BW
    t_coll = h["collective_total_bytes"] * f32_factor / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    n_chips = 512 if rec["mesh"] == "multi" else 256
    hlo_global = h["dot_flops_per_device"] * n_chips
    ratio = rec["model_flops_global"] / max(hlo_global, 1)
    # roofline fraction: useful model flops vs what the bottleneck term
    # would allow in the same wall time
    t_bound = max(terms.values())
    t_model_ideal = rec["model_flops_global"] / n_chips / PEAK_FLOPS
    frac = t_model_ideal / t_bound if t_bound > 0 else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "status": "ok",
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "bottleneck": bottleneck,
        "model_over_hlo_flops": ratio,
        "roofline_fraction": frac,
        "mem_temp_GB": (rec["memory"]["temp_bytes"] or 0) / 2 / 1e9,
        "compile_s": rec.get("compile_s"),
    }


def main():
    out = []
    for rec in load_cells():
        row = roofline_row(rec)
        if row.get("status") != "ok":
            out.append((f"roofline/{row['arch']}/{row['shape']}/{row['mesh']}",
                        0.0, f"status={row['status']}"))
            continue
        out.append((
            f"roofline/{row['arch']}/{row['shape']}/{row['mesh']}",
            row["t_compute_s"] * 1e6,
            f"bottleneck={row['bottleneck']};"
            f"tc={row['t_compute_s']:.3e};tm={row['t_memory_s']:.3e};"
            f"tx={row['t_collective_s']:.3e};"
            f"frac={row['roofline_fraction']:.3f};"
            f"model/hlo={row['model_over_hlo_flops']:.3f}"))
    return out


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.1f},{derived}")
