"""Regenerate derived experiment artifacts.

Default: the §Roofline table in EXPERIMENTS.md from results/dryrun.

``--bench``: refresh the committed ``BENCH_gnn_batched.json`` /
``BENCH_gnn_dist.json`` / ``BENCH_offload.json`` /
``BENCH_autoprec.json`` / ``BENCH_serve.json`` /
``BENCH_compressor.json`` baselines by re-running the plan-routed GNN
benchmark suites (each lowers explicit
:class:`repro.engine.plan.ExecutionPlan` objects through ``engine.run``,
so the refreshed numbers describe exactly what the engine executes) plus
the kernel-throughput sweep (which records the fused matmul-quant rows).
Run this on the CI-class machine whenever an intentional change moves
the columns ``scripts/bench_regression.py`` gates.

A CPU-only tool: the suites time the CPU backend, and ``gnn_dist`` runs
in a subprocess that forces virtual host devices, which a TPU host's
one-process-per-chip rule forbids.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from benchmarks.roofline import load_cells, roofline_row  # noqa: E402

ARCH_ORDER = [
    "seamless-m4t-large-v2", "qwen3-moe-235b-a22b", "arctic-480b",
    "qwen1.5-4b", "qwen1.5-32b", "mistral-nemo-12b", "qwen3-32b",
    "internvl2-2b", "mamba2-780m", "zamba2-1.2b"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def fmt(x, p=3):
    if x is None:
        return "-"
    return f"{x:.{p}e}" if (abs(x) < 1e-3 or abs(x) >= 1e4) else f"{x:.{p}f}"


def refresh_bench_baselines():
    """Re-run the engine-routed bench suites; they rewrite the committed
    BENCH_*.json in place (the bench-regression gate's baselines)."""
    from benchmarks import (autoprec, gnn_batched, kernel_throughput,
                            offload, serve)

    for tag, fn in [("gnn_batched", gnn_batched.main),
                    ("autoprec", autoprec.main), ("offload", offload.main),
                    ("serve", serve.main),
                    ("kernel", kernel_throughput.main)]:
        print(f"refreshing {tag} baseline ...")
        for name, us, derived in fn():
            print(f"  {name},{us:.1f},{derived}")
    # gnn_dist needs its forced-8-device XLA flag set BEFORE jax
    # initializes, so it refreshes in a subprocess (the script forces the
    # flag itself when run as __main__)
    import subprocess
    print("refreshing gnn_dist baseline (subprocess, forced 8 devices) ...")
    repo = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(repo / "benchmarks" / "gnn_dist.py")],
        capture_output=True, text=True, check=True,
        env={**__import__("os").environ,
             "PYTHONPATH": str(repo / "src") + ":" + str(repo)})
    for line in out.stdout.strip().splitlines():
        print(f"  {line}")


def main():
    rows = {(r["arch"], r["shape"], r["mesh"]): roofline_row(r)
            for r in load_cells()}
    lines = [
        "| arch | shape | t_compute (s) | t_memory (s) | t_collective (s) |"
        " bottleneck | roofline frac | MODEL/HLO flops | HBM temp GB/dev |"
        " compile (s) |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r = rows.get((arch, shape, "single"))
            if r is None:
                continue
            if r.get("status") != "ok":
                lines.append(f"| {arch} | {shape} | - | - | - | "
                             f"*{r.get('reason', r.get('status'))}* | - | - |"
                             f" - | - |")
                continue
            lines.append(
                f"| {arch} | {shape} | {fmt(r['t_compute_s'])} | "
                f"{fmt(r['t_memory_s'])} | {fmt(r['t_collective_s'])} | "
                f"**{r['bottleneck']}** | {r['roofline_fraction']:.3f} | "
                f"{r['model_over_hlo_flops']:.3f} | "
                f"{r['mem_temp_GB']:.2f} | {r['compile_s']} |")
    # multi-pod summary: every cell must compile; report worst deltas
    ok_multi = sum(1 for (a, s, m), r in rows.items()
                   if m == "multi" and r.get("status") == "ok")
    skip_multi = sum(1 for (a, s, m), r in rows.items()
                     if m == "multi" and r.get("status") == "skipped")
    lines.append("")
    lines.append(f"Multi-pod `(2,16,16)` pass: {ok_multi} compiled ok, "
                 f"{skip_multi} designed skips (same gate). Per-cell "
                 f"multi-pod terms are in `results/dryrun/*__multi.json`; "
                 f"the pod axis adds cross-pod DP gradient all-reduce — "
                 f"visible as increased collective bytes on train cells.")
    table = "\n".join(lines)

    exp = Path(__file__).resolve().parents[1] / "EXPERIMENTS.md"
    text = exp.read_text()
    marker = "TABLE-PLACEHOLDER (filled by scripts/refresh_experiments.py)"
    if marker in text:
        text = text.replace(marker, table)
    else:
        import re
        text = re.sub(r"(## §Roofline\n\n.*?\n\n)\|.*?\n\n(?=##|Multi-pod)",
                      r"\1" + table + "\n\n", text, flags=re.S)
        if "| arch | shape |" not in text:
            print("WARNING: could not splice table; appending")
            text += "\n\n" + table
    exp.write_text(text)
    print(f"wrote roofline table: {len(lines)} lines")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", action="store_true",
                    help="refresh the committed BENCH_*.json baselines "
                         "instead of the EXPERIMENTS.md roofline table")
    args = ap.parse_args()
    if args.bench:
        refresh_bench_baselines()
    else:
        main()
