"""Multi-pod dry-run: lower + compile every (arch × shape) on the
single-pod (16,16) and multi-pod (2,16,16) production meshes, record
memory_analysis / cost_analysis / collective bytes (parsed from optimized
HLO) into results/dryrun/*.json.

A CPU-only tool: importing it forces 512 virtual host devices (before JAX
initializes) and the production meshes are laid out over them.  Run it
with ``JAX_PLATFORMS=cpu``; it spawns a subprocess per cell, so it stays
off the chip path, where one process holds the chip.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # subprocess per cell
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import subprocess
import sys
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train (N = active params), 2·N·D fwd."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.batch  # decode: one token per sequence


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               act_mode: str | None = None):
    """Construct (step_fn, args shape structs, in_shardings) for a cell.

    ``act_mode`` overrides the config's activation policy (e.g. "act" lowers
    the paper's INT2 compressed-stash variant for before/after comparison).
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, cell_applicable, get, input_specs
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import (make_prefill_step, make_serve_step,
                                    make_train_step)
    from repro.models import Model
    from repro.optim import AdamWConfig, adamw_init
    from repro.parallel import annotate
    from repro.parallel.sharding import (batch_pspecs, cache_pspecs,
                                         param_pspecs, to_named)

    cfg = get(arch)
    if act_mode:
        from repro.core.compressor import CompressionConfig

        cfg = dataclasses.replace(
            cfg, act_mode=act_mode,
            act_compression=CompressionConfig(bits=2, group_size=256))
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return None, why

    mesh = make_production_mesh(multi_pod=multi_pod)
    annotate.set_rules(**annotate.rules_for(
        cfg, mesh, shape.batch, is_train=shape.kind == "train"))
    model = Model(cfg)
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_specs = to_named(param_pspecs(cfg, params_shape, mesh), mesh)
    specs = input_specs(cfg, shape)

    big = cfg.param_count() > 6e10  # bf16 optimizer moments for the giants
    opt = AdamWConfig(lr=1e-4, weight_decay=0.1, grad_clip=1.0,
                      state_dtype="bfloat16" if big else "float32")

    if shape.kind == "train":
        step = make_train_step(
            model, opt,
            accum_dtype=jnp.bfloat16 if big else jnp.float32)
        opt_shape = jax.eval_shape(
            lambda p: adamw_init(p, opt), params_shape)
        o_specs = {"step": to_named(jax.sharding.PartitionSpec(), mesh),
                   "m": jax.tree.map(lambda s: s, p_specs),
                   "v": jax.tree.map(lambda s: s, p_specs)}
        b_specs = to_named(
            batch_pspecs(cfg, shape.kind, mesh, shape.batch), mesh)
        args = (params_shape, opt_shape, specs)
        shardings = (p_specs, o_specs, b_specs)
        fn = step
    elif shape.kind == "prefill":
        # cache sized to the prompt (+ any stub-frontend prefix)
        fn = make_prefill_step(model, max_seq=None)
        b_specs = to_named(
            batch_pspecs(cfg, shape.kind, mesh, shape.batch), mesh)
        args = (params_shape, specs)
        shardings = (p_specs, b_specs)
    else:  # decode
        fn = make_serve_step(model)
        cache_shape = specs["cache"]
        c_specs = to_named(cache_pspecs(cfg, cache_shape, mesh, shape.batch,
                                        shape.seq), mesh)
        dp_total = 32 if multi_pod else 16
        dp_ax = ("pod", "data") if multi_pod else ("data",)
        tok_spec = to_named(jax.sharding.PartitionSpec(
            dp_ax if shape.batch % dp_total == 0 else None, None), mesh)
        args = (params_shape, cache_shape,
                jax.ShapeDtypeStruct((shape.batch, 1), jnp.int32))
        shardings = (p_specs, c_specs, tok_spec)
    return (fn, args, shardings, mesh), ""


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             act_mode: str | None = None) -> dict:
    import jax

    t0 = time.time()
    multi_pod = mesh_kind == "multi"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "act_mode": act_mode, "status": "?", "ts": time.strftime("%F %T")}
    built, why = build_cell(arch, shape_name, multi_pod, act_mode)
    if built is None:
        rec.update(status="skipped", reason=why)
        return rec
    from repro.configs import SHAPES, get
    from repro.launch.hlo_analysis import analyze

    fn, args, shardings, mesh = built
    with mesh:
        lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    n_dev = len(jax.devices())
    loop_aware = analyze(hlo, n_devices=n_dev)
    cfg = get(arch)
    shape = SHAPES[shape_name]
    rec.update(
        status="ok",
        lower_s=round(t_lower - t0, 1),
        compile_s=round(t_compile - t_lower, 1),
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        cost_raw={"flops_body_once": cost.get("flops"),
                  "bytes_accessed_body_once": cost.get("bytes accessed")},
        hlo={"dot_flops_per_device": loop_aware["flops"],
             "hbm_bytes_per_device": loop_aware["hbm"],
             "collective_wire_bytes_per_device": loop_aware["coll"],
             "collective_total_bytes": loop_aware["coll_total"],
             "n_computations": loop_aware["n_computations"]},
        model_flops_global=model_flops(cfg, shape),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
        n_devices=n_dev,
    )
    return rec


ALL_ARCHS = [
    "seamless-m4t-large-v2", "qwen3-moe-235b-a22b", "arctic-480b",
    "qwen1.5-4b", "qwen1.5-32b", "mistral-nemo-12b", "qwen3-32b",
    "internvl2-2b", "mamba2-780m", "zamba2-1.2b",
]
ALL_SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--act-mode", default=None,
                    choices=[None, "none", "remat", "act"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)

    if not args.all:
        rec = run_cell(args.arch, args.shape, args.mesh, args.act_mode)
        suffix = f"__{args.act_mode}" if args.act_mode else ""
        out = RESULTS / f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json"
        out.write_text(json.dumps(rec, indent=1))
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "status")}, indent=None))
        if rec["status"] == "ok":
            h = rec["hlo"]
            ratio = rec["model_flops_global"] / max(
                h["dot_flops_per_device"] * rec["n_devices"], 1)
            print(f"  lower {rec['lower_s']}s compile {rec['compile_s']}s "
                  f"dot_flops/dev={h['dot_flops_per_device']:.3e} "
                  f"model/hlo={ratio:.3f} "
                  f"coll/dev={h['collective_total_bytes']:.3e}B "
                  f"hbm/dev={h['hbm_bytes_per_device']:.3e}B")
        return 0 if rec["status"] in ("ok", "skipped") else 1

    # driver: one subprocess per cell (isolates compile memory, survives
    # single-cell crashes)
    failures = []
    for mesh_kind in ("single", "multi"):
        for arch in ALL_ARCHS:
            for shape in ALL_SHAPES:
                out = RESULTS / f"{arch}__{shape}__{mesh_kind}.json"
                if args.skip_done and out.exists():
                    st = json.loads(out.read_text()).get("status")
                    if st in ("ok", "skipped"):
                        continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh_kind]
                print(f"=== {arch} × {shape} × {mesh_kind}", flush=True)
                try:
                    r = subprocess.run(cmd, timeout=args.timeout)
                    if r.returncode != 0:
                        failures.append((arch, shape, mesh_kind, r.returncode))
                        out.write_text(json.dumps(
                            {"arch": arch, "shape": shape, "mesh": mesh_kind,
                             "status": "error", "rc": r.returncode}))
                except subprocess.TimeoutExpired:
                    failures.append((arch, shape, mesh_kind, "timeout"))
                    out.write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mesh_kind,
                         "status": "timeout"}))
    print(f"done; {len(failures)} failures: {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
