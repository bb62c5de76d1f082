"""Distributed-GNN benchmark: the mesh-sharded partition-parallel engine
vs the single-device full-graph engine at equal compression config.

A CPU-only tool: it forces virtual host devices and times the CPU
backend, so no number it writes is a device number.  On a TPU host the
four-chip mesh phase of ``chip_smoke.py --four-chips`` is the check.

Run standalone (``PYTHONPATH=src python benchmarks/gnn_dist.py``) this
module forces an 8-device host platform *before* jax initializes, so the
4-partition arm actually shards over 4 devices with a live halo exchange
and feature pager; imported into ``benchmarks/run.py``'s in-process
suite it uses whatever devices exist (a 1-device mesh degenerates to the
round-sequential engine — every metric below still exists).

``BENCH_gnn_dist.json`` rows:

* per-epoch wall time, both arms;
* halo traffic bytes/epoch (the ``all_to_all`` volume the ledger model
  predicts — 0 on a 1-device mesh);
* feature-pager prefetch overlap fraction (copy time hidden behind
  round compute);
* per-device peak saved-activation bytes: the deterministic stash-plan
  ledger (`mesh_stash_plan` vs the full-graph plan — the ISSUE 7 >=2x
  acceptance gate is CI-checked on this number in
  ``tests/test_parallel.py``), plus best-effort *measured* live bytes.

The regression gate (``scripts/bench_regression.py``) reads only the
device-count-independent metrics (epoch times, ledger bytes).
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__" and "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import json
import pathlib

JSON_PATH = (pathlib.Path(__file__).resolve().parent.parent
             / "BENCH_gnn_dist.json")


def run_bench(scale: float = 5e-5, epochs: int = 8, n_parts: int = 4,
              hidden=(128,)):
    import jax

    from repro.core import CompressionConfig
    from repro.engine import ExecutionPlan, SamplingPolicy, run
    from repro.engine.forward import mesh_stash_plan, plan_gnn_stashes
    from repro.graph import GNNConfig, papers100m_like
    from repro.offload import measure_live_bytes
    from repro.parallel.halo import build_halo_program

    g = papers100m_like(scale)
    comp = CompressionConfig(bits=2, group_size=32)
    cfg = GNNConfig(arch="gcn", hidden=hidden, n_classes=g.num_classes,
                    compression=comp)

    full_plan = ExecutionPlan()
    full = run(g, cfg, full_plan, n_epochs=epochs, seed=0)
    full_live = measure_live_bytes()

    mesh_plan = ExecutionPlan(sampling=SamplingPolicy(
        kind="mesh", n_parts=n_parts, shuffle=False))
    mesh = run(g, cfg, mesh_plan, n_epochs=epochs, seed=0)
    mesh_live = measure_live_bytes()

    prog = build_halo_program(g, n_parts, mesh["mesh_devices"])
    full_ledger = plan_gnn_stashes(cfg, g.n_feats, g.n_nodes).total_bytes
    dev_ledger = mesh_stash_plan(cfg, g.n_feats, prog.n_pad).total_bytes

    data = {
        "graph": {"name": g.name, "n_nodes": g.n_nodes,
                  "n_edges": g.n_edges, "n_feats": g.n_feats,
                  "n_parts": n_parts, "epochs": epochs},
        "devices": jax.device_count(),
        "mesh_devices": mesh["mesh_devices"],
        "rounds_per_epoch": mesh["updates_per_epoch"],
        "full_epoch_s": 1.0 / max(full["epochs_per_sec"], 1e-9),
        "mesh_epoch_s": 1.0 / max(mesh["epochs_per_sec"], 1e-9),
        "full_test_acc": full["test_acc"],
        "mesh_test_acc": mesh["test_acc"],
        "halo_width": mesh["halo_width"],
        "halo_bytes_per_epoch": mesh["halo_bytes_per_epoch"],
        "dropped_edges": mesh["dropped_edges"],
        "prefetch_overlap_frac": mesh["pager"]["overlap_frac"],
        "pager_host_bytes": mesh["pager"]["host_bytes"],
        "full_saved_bytes_ledger": full_ledger,
        "per_device_saved_bytes_ledger": dev_ledger,
        "per_device_peak_ratio": full_ledger / dev_ledger,
        # best-effort measured numbers (allocator-visible, CPU included)
        "full_measured_live_bytes": full_live,
        "mesh_measured_live_bytes": mesh_live,
    }
    JSON_PATH.write_text(json.dumps(data, indent=2))
    return data


def main(fast: bool = True):
    d = run_bench(scale=2e-5 if fast else 5e-5, epochs=4 if fast else 8)
    rows = []
    for arm in ("full", "mesh"):
        rows.append((
            f"gnn_dist/{arm}", d[f"{arm}_epoch_s"] * 1e6,
            f"acc={d[f'{arm}_test_acc']:.4f};"
            f"dev_peak_ratio={d['per_device_peak_ratio']:.2f};"
            f"halo_MB={d['halo_bytes_per_epoch'] / 1e6:.2f};"
            f"overlap={d['prefetch_overlap_frac']:.2f}"))
    return rows


if __name__ == "__main__":
    fast = "--fast" in sys.argv
    for name, us, derived in main(fast=fast):
        print(f"{name},{us:.1f},{derived}")
