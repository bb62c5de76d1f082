#!/usr/bin/env python3
"""Chip smoke test: full-graph i-EXACT GNN training on one TPU.

Drives the library's main path, ``repro.graph.train_gnn`` with the default
``ExecutionPlan``, at the published arxiv width (169,343 nodes, ~1.17M
edges, 128 features, 40 classes; GraphSAGE with hidden (256, 256)), with
random weights from seed 0, and checks what comes out:

(a) device: JAX's first device must be a TPU; anything else fails at once;
(b) kernel parity: the Pallas quantize/dequantize kernels against the jnp
    reference on a real activation (SAGE layer-0 input, 169,343 x 256 f32,
    regrouped at G=256) for INT2, INT8 and INT2 with VM levels: packed
    words, zero and range bit-identical, dequantized values within
    ``DEQUANT_ATOL``; the RP kernel within ``RP_REL_TOL`` of the matmul;
(c) training: ``EPOCHS`` epochs as fp32 (the reference), the paper's
    i-EXACT config and the fused-kernel config; every loss finite, each
    compressed run's final training loss within ``LOSS_ATOL`` of fp32, every
    quantize routed to a Pallas kernel, and each compressed epoch step
    compiled with a ``tpu_custom_call``;
(d) reports, one line each: device, per-layer kernel routes, compile time,
    warm epoch time and peak device bytes per phase;
(e) the last line of stdout is
    ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Any failed check exits non-zero without that line.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # mesh engine on four chips only

``--four-chips`` runs the mesh-sharded engine on
``papers100m_like(MESH_SCALE)`` (4 partitions on 4 devices, fp32 and INT2)
against the single-device full-graph engine in fp32, and nothing else.

Everything runs in this one process: a child that touched JAX could not
reach the chip this process holds.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

#: Training epochs per config: enough for fp32 to drive the training loss
#: from ln(40) ~ 3.7 to near zero on this graph.
EPOCHS = 20
#: Warm epochs timed per config, after the compile.
WARM_EPOCHS = 5
#: Final training loss of a compressed run vs fp32, absolute (nats).  The
#: SR and RP estimators are unbiased, so a compressed run converges to the
#: same place, RP=8 a few epochs later: after 20 epochs on arxiv_like at
#: scales 0.1 and 0.3 (CPU, jnp reference, codes identical to the kernels)
#: fp32 ends at 0.002-0.003, INT2+RP8 at 0.006-0.007 and fused INT2 within
#: 0.0003 of fp32.  A wrong code, scale or sign keeps the loss of order 1,
#: far outside 0.05 nats (1.4% of the initial loss).
LOSS_ATOL = 0.05
#: Dequantized values, Pallas vs jnp.  Both evaluate
#: ``level * (range / B) + zero`` in float32 from bit-identical inputs;
#: only contraction into a fused multiply-add can differ, which moves a
#: value by at most one rounding of a term no larger than |zero| + range
#: (a few units for these activations), i.e. well under 1e-5.
DEQUANT_ATOL = 1e-5
#: RP kernel vs a float32 matmul at highest precision, relative Frobenius
#: error.  An MXU pass may round the float32 operands to bfloat16 (2^-8
#: relative per product); a wrong sign pattern gives an error near sqrt(2).
RP_REL_TOL = 1e-2
#: Four-chip phase.  At papers100m_like(0.01) (33M edges) one SAGE layer's
#: edge messages alone take 34 GB, so the single-chip full-graph reference
#: cannot exist on a 16 GB v5e; at 0.002 (222K nodes, 6.7M edges) its epoch
#: step compiles to 8.5 GB of temporaries.
MESH_SCALE = 0.002
MESH_HIDDEN = (256, 256)
MESH_EPOCHS = 5
#: Mesh engine with one partition per device (every edge kept) vs the
#: single-device full-graph engine, fp32 at highest matmul precision, final
#: training loss, relative: the same arithmetic in another reduction order
#: (psum across chips, per-partition scatter) over 5 Adam steps.
MESH_LOSS_RTOL = 1e-3

SEED = 0
QUANT_SEED = 1234


def _log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def tpu_devices(count: int):
    """Phase (a): the platform must be a TPU with at least ``count`` chips."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) >= count, f"need {count} TPU chips, found {len(devs)}")
    _log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
         f"count={len(devs)}")
    return devs


def peak_bytes() -> int:
    from repro.offload import device_memory_stats

    return int(device_memory_stats()["peak_bytes_in_use"])


def sage_layer0_input(g):
    """The real 256-wide activation GraphSAGE stashes at layer 0:
    ``concat(h, mean-aggregate(h))`` over the graph's features."""
    import jax.numpy as jnp

    from repro.graph.models import spmm

    agg = spmm(g.features, g.aggregation[1])
    return jnp.concatenate([g.features, agg], axis=1)


def kernel_parity(g) -> None:
    """Phase (b): Pallas vs jnp on a real-width activation."""
    import jax
    import numpy as np

    from repro.core.compressor import CompressionConfig
    from repro.kernels import ops

    x = sage_layer0_input(g)
    blocks = x.reshape(-1, 256)
    _log(f"parity: activation {tuple(x.shape)} -> {blocks.shape[0]} blocks "
         "of G=256")
    for name, bits, levels in (
            ("int2", 2, None), ("int8", 8, None),
            ("int2-vm", 2, CompressionConfig(2, 256, vm=True).levels())):
        out = {impl: jax.block_until_ready(ops.quantize_packed(
            blocks, bits, QUANT_SEED, levels, impl=impl))
            for impl in ("pallas", "jnp")}
        for field, a, b in zip(("packed", "zero", "range"),
                               out["pallas"], out["jnp"]):
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  f"parity {name}: {field} differs between pallas and jnp")
        packed, zero, rng = out["jnp"]
        deq = {impl: np.asarray(ops.dequantize_packed(
            packed, zero, rng, bits, 256, levels, impl=impl))
            for impl in ("pallas", "jnp")}
        err = float(np.max(np.abs(deq["pallas"] - deq["jnp"])))
        check(err <= DEQUANT_ATOL,
              f"parity {name}: dequantized max|diff| {err} > {DEQUANT_ATOL}")
        _log(f"parity {name}: packed/zero/range bit-identical, dequant "
             f"max|diff|={err:.3g} (atol {DEQUANT_ATOL})")
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(ops.rp_project(x, QUANT_SEED, 128, impl="jnp"))
    got = np.asarray(ops.rp_project(x, QUANT_SEED, 128, impl="pallas"))
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    check(rel <= RP_REL_TOL, f"parity rp 256->128: relative error {rel} > "
                             f"{RP_REL_TOL}")
    _log(f"parity rp 256->128: relative error {rel:.3g} (tol {RP_REL_TOL})")


def layer_routes(cfg, n_nodes: int, in_dim: int, fused: str) -> list[dict]:
    """Phase (d): the kernel each layer's quantize, RP and fused matmul
    resolves to, with the reason wherever it is not a Pallas kernel."""
    from repro.core import backend
    from repro.graph.models import _dims

    dims = _dims(cfg, in_dim)
    rows = []
    for li, (d_in, comp) in enumerate(zip(dims[:-1],
                                          cfg.layer_compression())):
        width = d_in * (2 if cfg.arch == "sage" else 1)
        if comp is None:
            rows.append({"layer": li, "width": width, "quant": "none"})
            continue
        levels = comp.levels()
        row = {"layer": li, "width": width}
        concrete = backend.route_fused(fused, comp.impl, (n_nodes, width),
                                       comp.bits, comp.group_size, levels,
                                       comp.rp_ratio)
        row["fused"] = concrete or "off"
        if concrete is None:
            row["fused_reason"] = backend.fused_decline_reason(
                fused, comp.impl, (n_nodes, width), comp.bits,
                comp.group_size, levels, comp.rp_ratio)
        if comp.rp_ratio > 1:
            r = width // comp.rp_ratio
            row["rp"] = backend.route_rp(comp.impl, width, r)
            reason = backend.rp_kernel_unsupported(width, r)
            if reason:
                row["rp_reason"] = reason
        row["quant"] = concrete or backend.route_quant(
            comp.impl, comp.bits, comp.group_size, levels)
        rows.append(row)
    return rows


def measure_epoch_step(g, cfg, fused: str, opt):
    """Compile the plan's epoch step ahead of time: (compile seconds,
    median warm epoch seconds, whether the step holds a Pallas kernel)."""
    import jax
    import jax.numpy as jnp

    from repro.engine.compile import compile_plan
    from repro.engine.plan import ExecutionPlan
    from repro.graph.models import init_gnn_params
    from repro.optim import adamw_init

    plan = ExecutionPlan.from_legacy(fused=fused)
    cfg = plan.kernel.apply(cfg)
    params = init_gnn_params(jax.random.PRNGKey(SEED), cfg, g.n_feats)
    state = adamw_init(params, opt)
    comp = compile_plan(g, cfg, plan, opt)
    data = comp.epoch_data(None)
    t0 = time.perf_counter()
    exe = comp.step.lower(params, state, jnp.asarray(0), *data).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in exe.as_text()
    params, state, loss = exe(params, state, jnp.asarray(0), *data)
    jax.block_until_ready(loss)
    times = []
    for epoch in range(1, WARM_EPOCHS + 1):
        t0 = time.perf_counter()
        params, state, loss = exe(params, state, jnp.asarray(epoch), *data)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
    return compile_s, statistics.median(times), has_kernel


def train_phase(name: str, g, cfg, fused: str, opt) -> float:
    """Phase (c) for one config: routes, compiled step, then ``train_gnn``.
    Returns the final training loss."""
    import numpy as np

    from repro.graph.train import train_gnn

    for row in layer_routes(cfg, g.n_nodes, g.n_feats, fused):
        _log(f"route {name}: " + " ".join(f"{k}={v}" for k, v in row.items()))
        if cfg.compression is not None:
            check(row["quant"] == "pallas",
                  f"{name}: layer {row['layer']} quantizes on "
                  f"{row['quant']!r}, not the Pallas kernel")
    compile_s, epoch_s, has_kernel = measure_epoch_step(g, cfg, fused, opt)
    if cfg.compression is not None:
        check(has_kernel, f"{name}: compiled epoch step has no "
                          "tpu_custom_call")
    res = train_gnn(g, cfg, opt, n_epochs=EPOCHS, seed=SEED, fused=fused,
                    verbose=True, eval_every=1)
    losses = [loss for _, loss, _ in res["history"]]
    check(len(losses) == EPOCHS and all(np.isfinite(losses)),
          f"{name}: non-finite loss in {losses}")
    _log(f"train {name}: compile_s={compile_s:.3f} warm_epoch_s={epoch_s:.6f} "
         f"tpu_custom_call={has_kernel} loss {losses[0]:.5f} -> "
         f"{losses[-1]:.5f} val_acc={res['val_acc']:.4f} "
         f"peak_bytes_in_use={peak_bytes()}")
    return losses[-1]


def one_chip() -> None:
    from repro.core.compressor import CompressionConfig
    from repro.graph.data import arxiv_like
    from repro.graph.models import GNNConfig
    from repro.optim import AdamWConfig

    g = arxiv_like(scale=1.0, seed=SEED)
    _log(f"graph: arxiv_like(1.0) nodes={g.n_nodes} "
         f"edges={len(g.edge_src)} feats={g.n_feats} "
         f"classes={g.num_classes}")
    check(g.n_nodes == 169_343, f"arxiv_like(1.0) has {g.n_nodes} nodes")
    kernel_parity(g)
    _log(f"phase parity: peak_bytes_in_use={peak_bytes()}")

    opt = AdamWConfig(lr=5e-3, weight_decay=0.0)
    configs = (
        ("fp32", None, "auto"),
        ("iexact-int2-g256-rp8", CompressionConfig(2, 256, 8, impl="pallas"),
         "auto"),
        ("fused-int2-g128", CompressionConfig(2, 128, 0, impl="pallas"),
         "on"),
    )
    final = {}
    for name, comp, fused in configs:
        cfg = GNNConfig(arch="sage", hidden=(256, 256),
                        n_classes=g.num_classes, compression=comp)
        final[name] = train_phase(name, g, cfg, fused, opt)
    ref = final["fp32"]
    for name, loss in final.items():
        gap = abs(loss - ref)
        check(gap <= LOSS_ATOL, f"{name}: final loss {loss:.5f} is {gap:.5f} "
                                f"from fp32 {ref:.5f} (tol {LOSS_ATOL})")
        _log(f"loss vs fp32 {name}: {loss:.5f} vs {ref:.5f} "
             f"(|diff| {gap:.5f}, tol {LOSS_ATOL})")


def four_chips() -> None:
    """Mesh engine on four chips vs the full-graph engine on one."""
    import jax
    import numpy as np

    from repro.core.compressor import CompressionConfig
    from repro.graph.data import papers100m_like
    from repro.graph.models import GNNConfig
    from repro.graph.train import train_gnn, train_gnn_mesh

    g = papers100m_like(scale=MESH_SCALE, seed=SEED)
    _log(f"graph: papers100m_like({MESH_SCALE}) nodes={g.n_nodes} "
         f"edges={len(g.edge_src)} feats={g.n_feats} "
         f"classes={g.num_classes}")

    def trained(name, comp, highest: bool, mesh: bool) -> float:
        cfg = GNNConfig(arch="sage", hidden=MESH_HIDDEN,
                        n_classes=g.num_classes, compression=comp)
        precision = "highest" if highest else None
        t0 = time.perf_counter()
        with jax.default_matmul_precision(precision):
            if mesh:
                res = train_gnn_mesh(g, cfg, 4, n_epochs=MESH_EPOCHS,
                                     seed=SEED, verbose=True, eval_every=1)
            else:
                res = train_gnn(g, cfg, n_epochs=MESH_EPOCHS, seed=SEED,
                                verbose=True, eval_every=1)
        wall = time.perf_counter() - t0
        losses = [loss for _, loss, _ in res["history"]]
        check(len(losses) == MESH_EPOCHS and all(np.isfinite(losses)),
              f"{name}: non-finite loss in {losses}")
        extra = ""
        if mesh:
            check(res["mesh_devices"] == 4,
                  f"{name}: mesh has {res['mesh_devices']} devices, not 4")
            extra = (f"mesh_devices={res['mesh_devices']} "
                     f"dropped_edges={res['dropped_edges']} "
                     f"halo_width={res['halo_width']} ")
        _log(f"train {name}: {extra}wall_s={wall:.3f} loss {losses[0]:.6f} "
             f"-> {losses[-1]:.6f} val_acc={res['val_acc']:.4f} "
             f"peak_bytes_in_use={peak_bytes()}")
        return losses[-1]

    got = trained("mesh4-fp32", None, highest=True, mesh=True)
    trained("mesh4-int2", CompressionConfig(2, 256, 8, impl="pallas"),
            highest=False, mesh=True)
    ref = trained("full-fp32", None, highest=True, mesh=False)
    gap = abs(got - ref) / ref
    check(gap <= MESH_LOSS_RTOL, f"mesh4-fp32 final loss {got:.6f} is "
                                 f"{gap:.3%} from full-graph {ref:.6f}")
    _log(f"loss mesh4-fp32 vs full-fp32: {got:.6f} vs {ref:.6f} "
         f"(rel {gap:.4%}, tol {MESH_LOSS_RTOL:.1%})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    devs = tpu_devices(4 if args.four_chips else 1)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    _log(f"compile cache: {enable_compile_cache()}")
    four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
