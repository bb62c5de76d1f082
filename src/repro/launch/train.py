"""End-to-end training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \\
      --steps 50 --act-mode act --ckpt-dir /tmp/run1

Full configs need the production mesh (TPU pod); ``--smoke`` runs the
reduced same-family config on local devices.  Auto-resumes from the last
checkpoint in --ckpt-dir.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get, reduce_for_smoke
from repro.core.compressor import CompressionConfig
from repro.data import batch_for_step
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.launch.steps import make_train_step
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init
from repro.obs import ObsPolicy
from repro.obs.trace import stopwatch
from repro.parallel import annotate
from repro.parallel.sharding import batch_pspecs, param_pspecs, to_named
from repro.runtime import StragglerMonitor, TrainRunner
from repro.runtime.compile_cache import enable_compile_cache


def _graph_main(args):
    """--graph-batches path: the partition-sampled GNN engine instead of an
    LM arch (same launcher, same compression flags, same mesh plumbing).

    Flags lower onto one :class:`~repro.engine.plan.ExecutionPlan`; the
    engine run and the memory report read the *same* plan object, so the
    byte/bit accounting describes exactly what this invocation stashed."""
    from repro.engine import run as engine_run
    from repro.engine.plan import (ExecutionPlan, KernelPolicy,
                                   SamplingPolicy)
    from repro.graph import (GNNConfig, activation_memory_report, arxiv_like,
                             flickr_like, papers100m_like)

    maker = {"arxiv": arxiv_like, "flickr": flickr_like,
             "papers100m": papers100m_like}[args.graph_dataset]
    g = maker(scale=args.graph_scale)
    comp = None
    if args.act_mode == "act":
        comp = CompressionConfig(bits=args.act_bits, group_size=args.act_group,
                                 rp_ratio=8, impl=args.act_impl)
    cfg = GNNConfig(arch=args.graph_arch, hidden=(256, 256),
                    n_classes=g.num_classes, compression=comp)
    lr = args.lr if args.lr is not None else 5e-3   # GNN engines' default
    offload = None if args.offload == "none" else args.offload
    obs_policy = ObsPolicy()
    if args.obs:
        obs_policy = ObsPolicy(enabled=True,
                               quant_stats=comp is not None,
                               quant_stats_every=args.obs_quant_every)
    if args.mesh_parts:
        # mesh-sharded partition-parallel engine: the graph mesh is built
        # by the compiler (largest divisor of n_parts the host allows);
        # stash/precision knobs belong to the other engines and raise
        plan = ExecutionPlan(
            sampling=SamplingPolicy(kind="mesh", n_parts=args.mesh_parts,
                                    shuffle=False),
            kernel=KernelPolicy(fused=args.act_fused),
            obs=obs_policy)
        mesh = None
    else:
        mesh = (make_production_mesh() if args.production_mesh
                else make_local_mesh())
        plan = ExecutionPlan.from_legacy(
            n_parts=args.graph_batches, fused=args.act_fused,
            offload=offload, bit_budget=args.bit_budget,
            autoprec_refresh=args.autoprec_refresh, halo=args.graph_halo,
            obs=obs_policy)
    print(f"plan: {plan.describe()}")
    r = engine_run(g, cfg, plan, AdamWConfig(lr=lr, weight_decay=0.0),
                   n_epochs=args.steps, seed=0, verbose=True, mesh=mesh)
    if args.mesh_parts:
        pg = r["pager"]
        print(f"mesh: {r['mesh_devices']} devices x "
              f"{r['updates_per_epoch']} rounds, halo width "
              f"{r['halo_width']} rows, {r['dropped_edges']} cross-round "
              f"edges dropped, {r['halo_bytes_per_epoch'] / 1e6:.2f} MB "
              f"halo traffic/epoch")
        print(f"feature pager: {pg['host_bytes'] / 1e6:.2f} MB host-resident "
              f"in {pg['n_pages']} pages/round, overlap "
              f"{pg['overlap_frac']:.2f} (last {pg['overlap_window_size']} "
              f"fetches: {pg['overlap_frac_window']:.2f})")
    quant_rows = []
    obs = r.get("obs")
    if obs is not None:
        quant_rows = obs.quant_rows()
        if quant_rows:
            ep = quant_rows[0]["epoch"]
            print(f"quant health (epoch {ep}): layer bits measured "
                  "predicted ratio sat%")
            for row in quant_rows:
                print(f"  L{row['layer']} {row['bits']}b "
                      f"{row['measured_var']:.3e} "
                      f"{row['predicted_var']:.3e} "
                      f"{row['ratio']:.2f} {100 * row['sat_rate']:.1f}%")
        if args.trace_out:
            paths = obs.export(args.trace_out)
            print(f"obs trace: {paths['chrome']} (load at ui.perfetto.dev)")
    cfg = r.get("cfg", cfg)   # autoprec may have re-allocated per-layer bits
    rep = activation_memory_report(g, cfg, batch_nodes=r["batch_nodes"],
                                   plan=plan,
                                   quant_health=quant_rows or None)
    if "arena" in rep:
        a = rep["arena"]
        print(f"stash arena[{a['policy']}]: {a['planned_bytes'] / 1e6:.2f} MB "
              f"pooled ({a['u32_bytes'] / 1e6:.2f} u32 + "
              f"{a['f32_bytes'] / 1e6:.2f} f32), "
              f"device-resident {a['device_resident_bytes'] / 1e6:.2f} MB")
    if "bits_per_layer" in r:
        print(f"autoprec: budget={args.bit_budget} avg bits "
              f"({r['bit_budget_bytes']} stash bytes) -> per-layer bits "
              f"{r['bits_per_layer']}")
    print(f"{g.name}: {g.n_nodes} nodes -> {r['n_parts']} batches of "
          f"{r['batch_nodes']} padded nodes, "
          f"{r['updates_per_epoch']} updates/epoch")
    print(f"epochs={args.steps} val_acc={r['val_acc']:.4f} "
          f"test_acc={r['test_acc']:.4f} S={r['epochs_per_sec']:.2f} e/s")
    if "mesh" in rep:
        print(f"per-device peak saved-activation bytes: "
              f"{rep['mesh']['per_device_saved_bytes'] / 1e6:.2f} MB "
              f"({rep['mesh']['peak_reduction_vs_full']:.1f}x below "
              f"full-graph)")
    elif "batched" in rep:
        print(f"peak saved-activation bytes/batch: "
              f"{rep['batched']['peak_saved_bytes'] / 1e6:.2f} MB "
              f"({rep['batched']['peak_reduction_vs_full']:.1f}x below "
              f"full-graph)")
    else:
        full = rep.get("compressed_bytes", rep["fp32_bytes"])
        print(f"full-graph saved-activation bytes: {full / 1e6:.2f} MB")
    return r["history"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM config name (required unless --graph-batches)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=None,
                    help="defaults to 3e-4 (LM) / 5e-3 (--graph-batches)")
    ap.add_argument("--act-mode", default=None,
                    choices=[None, "none", "remat", "act"])
    ap.add_argument("--act-bits", type=int, default=2)
    ap.add_argument("--act-group", type=int, default=256)
    ap.add_argument("--act-impl", default="auto",
                    choices=["auto", "jnp", "interp", "pallas"],
                    help="kernel backend for the compression stack "
                         "(core.backend dispatch; 'auto' = pallas on TPU)")
    ap.add_argument("--act-fused", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused quantize-in-epilogue matmul pair for the "
                         "GNN engine (KernelPolicy.fused): 'auto' fuses "
                         "eligible layers on the real Pallas backend, "
                         "'on' forces it, 'off' keeps the two-pass path")
    ap.add_argument("--offload", default="none",
                    choices=["none", "device", "host", "pinned-paged"],
                    help="where saved-for-backward stashes live "
                         "(repro.offload): 'device' pools them in one "
                         "arena (--graph-batches path), 'host'/'pinned-"
                         "paged' additionally park segments in host "
                         "memory between forward and backward (LM path: "
                         "per-layer host stash under the scan)")
    ap.add_argument("--opt-bits", type=int, default=0, choices=[0, 8])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure (fault-tolerance demo/tests)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--graph-batches", type=int, default=0, metavar="N_PARTS",
                    help="train the GNN stack with the partition-sampled "
                         "mini-batch engine (N_PARTS subgraph batches; "
                         "--steps counts epochs) instead of an LM arch")
    ap.add_argument("--mesh-parts", type=int, default=0, metavar="N_PARTS",
                    help="train the GNN stack with the mesh-sharded "
                         "partition-parallel engine: N_PARTS partitions "
                         "sharded over a 'graph' device mesh axis with "
                         "per-layer halo exchange and host-resident "
                         "feature paging (--steps counts epochs)")
    ap.add_argument("--graph-dataset", default="arxiv",
                    choices=["arxiv", "flickr", "papers100m"])
    ap.add_argument("--graph-scale", type=float, default=0.02)
    ap.add_argument("--graph-arch", default="sage", choices=["sage", "gcn"])
    ap.add_argument("--graph-halo", type=int, default=0,
                    help="hops of in-neighborhood halo around each partition")
    ap.add_argument("--bit-budget", type=float, default=None,
                    help="variance-guided adaptive precision: average stash "
                         "bits per element (2.0 = the fixed-INT2 footprint); "
                         "per-layer widths are solved by core.autoprec "
                         "(--graph-batches path)")
    ap.add_argument("--autoprec-refresh", type=int, default=0,
                    help="re-collect sensitivity stats and re-solve the "
                         "allocation every N epochs (0 = allocate once)")
    ap.add_argument("--obs", action="store_true",
                    help="enable the runtime observability layer "
                         "(repro.obs): engine spans, metrics, and — when "
                         "compression is on — the per-layer quant-health "
                         "probe (graph engines; bit-identical to obs-off)")
    ap.add_argument("--trace-out", default=None, metavar="BASE",
                    help="with --obs: export the span trace to "
                         "BASE.trace.json (Chrome trace_event — load at "
                         "ui.perfetto.dev)")
    ap.add_argument("--obs-quant-every", type=int, default=10, metavar="N",
                    help="with --obs: run the quant-health probe every N "
                         "epochs")
    args = ap.parse_args(argv)

    if args.graph_batches and args.mesh_parts:
        ap.error("--graph-batches and --mesh-parts are different engines; "
                 "pick one")
    if args.graph_batches or args.mesh_parts:
        return _graph_main(args)
    if args.arch is None:
        ap.error("--arch is required unless --graph-batches or "
                 "--mesh-parts is set")

    cfg = get(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if args.act_mode:
        comp = CompressionConfig(bits=args.act_bits, group_size=args.act_group,
                                 impl=args.act_impl)
        cfg = dataclasses.replace(cfg, act_mode=args.act_mode,
                                  act_compression=comp)
    if args.offload in ("host", "pinned-paged"):
        # "device" is a no-op for the LM path: without a multi-layer arena
        # the per-layer residual already is the device placement
        cfg = dataclasses.replace(cfg, act_offload=args.offload)

    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    annotate.set_rules(**annotate.rules_for(cfg, mesh, args.batch))

    model = Model(cfg)
    lr = args.lr if args.lr is not None else 3e-4
    opt = AdamWConfig(lr=lr, weight_decay=0.01, grad_clip=1.0,
                      warmup_steps=min(20, args.steps // 5),
                      state_bits=args.opt_bits)
    act_impl = None if args.act_impl == "auto" else args.act_impl
    train_step = make_train_step(model, opt, act_impl=act_impl)

    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        opt_state = adamw_init(params, opt)
        jit_step = jax.jit(train_step, donate_argnums=(0, 1))

        def step_fn(state, batch):
            params, opt_state = state
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            return (params, opt_state), metrics

        def make_batch(step):
            toks = batch_for_step(cfg.vocab, args.batch, args.seq, step)
            b = {"tokens": jnp.asarray(toks)}
            if cfg.family == "encdec":
                b["enc_embeds"] = jax.random.normal(
                    jax.random.PRNGKey(step), (args.batch, args.seq,
                                               cfg.d_model), jnp.bfloat16)
            if cfg.frontend == "vision":
                b["prefix_embeds"] = jax.random.normal(
                    jax.random.PRNGKey(step), (args.batch, cfg.frontend_len,
                                               cfg.d_model), jnp.bfloat16)
            return b

        if args.ckpt_dir:
            runner = TrainRunner(step_fn, make_batch, args.ckpt_dir,
                                 ckpt_every=args.ckpt_every,
                                 fail_at_step=args.fail_at,
                                 monitor=StragglerMonitor())
            state, hist = runner.run((params, opt_state), args.steps)
            print(f"straggler events: {len(runner.monitor.events)}")
        else:
            state = (params, opt_state)
            hist = []
            for step in range(args.steps):
                with stopwatch("lm/step", step=step) as sw:
                    state, m = step_fn(state, make_batch(step))
                hist.append({"step": step, "loss": float(m["loss"]),
                             "dt": sw.elapsed_s})
        first, last = hist[0]["loss"], hist[-1]["loss"]
        print(f"steps={len(hist)} loss {first:.4f} -> {last:.4f}")
        return hist


if __name__ == "__main__":
    enable_compile_cache()
    main()
