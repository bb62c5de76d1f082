"""One cell of ``BENCHMARK.json``, from set-up to the result line.

Everything cell-specific is found by name: the configuration's file
(``BENCHMARK.json`` names it), the traffic mix at
``bench/traffic/<traffic>.json``, the cell's correctness limits at
``bench/workloads/<cell>.json`` and each per-layer metric's reader at
``bench/metrics/<metric>.py``.

A traffic's ``mode`` is ``"full"`` (full-graph training on one chip) or
``"mesh"`` (the graph cut into ``n_parts`` partitions, one a chip, with a
halo exchange before each aggregation: every edge is kept, so the check
is the same full-graph reference).

A run:

1. set-up: builds the graph and the weights on the device from the seed,
   lowers the traffic's plan (``repro.engine.compile.compile_plan``),
   compiles its step (a full graph's epoch step, or the mesh's round
   step, which the plan's own epoch step then calls), reads the
   compiled program's memory analysis, and drives the plan's epoch step
   through its first three epochs, keeping what the check needs;
2. window: calls the same epoch step back to back for ``seconds``, one
   epoch in flight behind the one being waited on, and ends with
   ``block_until_ready``;
3. check: with the program's state freed, runs the plain reference's
   first three steps from the same graph and weights and compares.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int, require_tpu: bool = True) -> list:
    """JAX's devices, where they hold the cell's ``chips`` (and, with
    ``require_tpu``, are TPUs); :class:`NoChip` otherwise.  A mesh never
    falls back to fewer devices than its partitions: that would drop
    every edge between them and compute something else."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, "
                     "not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs


# ------------------------------------------------------------ files by name
def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_files(spec: dict, cell_name: str) -> dict:
    """The cell's entry with its configuration, traffic and limits."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise ValueError(f"no workload {cell_name!r} (known: "
                         f"{sorted(cells)})")
    cell = cells[cell_name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    own = json.loads((BENCH / "workloads" / f"{cell_name}.json").read_text())
    return {
        "cell": cell,
        "config": json.loads((ROOT / entry["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": own["limits"],
        "faults_caught": own["faults_caught"],
    }


def metrics_of(spec: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    out = []
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])}
    for m in spec[kind]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def load_reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- the program
def program_objects(files: dict, graph: dict):
    """The program's graph, model config, plan and optimizer for a cell."""
    import dataclasses

    from repro.core.compressor import CompressionConfig
    from repro.engine.plan import ExecutionPlan, SamplingPolicy
    from repro.graph.data import Graph
    from repro.graph.models import GNNConfig
    from repro.optim import AdamWConfig

    config, traffic = files["config"], files["traffic"]
    if traffic["mode"] not in ("full", "mesh"):
        raise ValueError(f"traffic mode {traffic['mode']!r}: 'full' or "
                         "'mesh' graph training is driven")
    if traffic["mode"] == "mesh":
        if traffic["n_parts"] != files["cell"]["chips"]:
            raise ValueError(
                f"a mesh traffic of {traffic['n_parts']} partitions on "
                f"{files['cell']['chips']} chips: the check is full-graph "
                "training, which only one partition a chip computes")
        if matmul_precision(files) != "default":
            raise ValueError("a mesh cell runs at the default matmul "
                             "precision: its round step compiles at its "
                             "first call")
    model, o = config["model"], config["optimizer"]
    if model["arch"] != "sage" or model["aggregation"] != "mean":
        raise ValueError(f"model {model}: the reference is mean-aggregation "
                         "GraphSAGE")
    g = Graph(name=config["name"], features=graph["features"],
              labels=graph["labels"], edge_src=graph["src"],
              edge_dst=graph["dst"], gcn_weight=graph["gcn_w"],
              mean_weight=graph["mean_w"], train_mask=graph["train"],
              val_mask=graph["val"], test_mask=graph["test"],
              num_classes=config["graph"]["n_classes"])
    comp = None
    if traffic["bits"] is not None:
        comp = CompressionConfig(bits=traffic["bits"],
                                 group_size=traffic["group_size"],
                                 rp_ratio=traffic["rp_ratio"],
                                 vm=traffic["vm"])
    plan = ExecutionPlan.from_legacy(fused=traffic["fused"])
    if traffic["mode"] == "mesh":
        plan = dataclasses.replace(plan, sampling=SamplingPolicy(
            kind="mesh", n_parts=traffic["n_parts"],
            method=traffic["method"]))
    cfg = plan.kernel.apply(GNNConfig(
        arch="sage", hidden=tuple(model["hidden"]),
        n_classes=config["graph"]["n_classes"], compression=comp))
    opt = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"])
    return g, cfg, plan, opt


def matmul_precision(files: dict) -> str:
    """The matmul precision the configuration states for float32."""
    return files["config"]["model"]["matmul_precision"]


def first_epoch(seed: int) -> int:
    """The epoch counter the run starts at: drawn from the seed, so the
    program's stochastic-rounding stream differs from seed to seed."""
    return (seed * 2654435761) % (1 << 24)


def _host(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda a: np.array(a, np.float32), tree)


# ------------------------------------------------------------------- a run
def _round_exe(comp, params, state, epoch: int):
    """The mesh's round step, compiled for round 0's operands.  The plan
    offers no public handle on it, so this reads the lowering's
    ``_round_step`` and ``_round_const``; the same lowering's first call
    finds this executable in JAX's cache and compiles nothing."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    feats = jax.ShapeDtypeStruct(comp.prog.features.shape[1:], jnp.float32,
                                 sharding=NamedSharding(comp.mesh,
                                                        P("graph")))
    return comp._round_step.lower(params, state, jnp.asarray(epoch),
                                  jnp.asarray(0), feats,
                                  *comp._round_const[0]).compile()


def build(files: dict, seed: int):
    """Set-up up to the compiled step: returns a namespace with the
    graph, the weights (and a host copy), the program's objects, the
    optimizer state, the compiled program (``exe``: the full graph's
    epoch step, or the mesh's round step) and the plan's epoch step
    (``step``: ``exe`` itself for a full graph)."""
    import types

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.engine.compile import compile_plan
    from repro.optim import adamw_init

    import graphgen

    b = types.SimpleNamespace()
    t = time.perf_counter()
    b.graph = graphgen.graph_from_config(files["config"], seed)
    b.params = graphgen.weights_from_config(files["config"], seed)
    b.params0 = _host(b.params)
    b.inputs_s = time.perf_counter() - t
    b.g, b.cfg, b.plan, b.opt = program_objects(files, b.graph)
    b.state = adamw_init(b.params, b.opt)
    b.comp = compile_plan(b.g, b.cfg, b.plan, b.opt)
    b.data = b.comp.epoch_data(None)
    b.epoch = first_epoch(seed)
    t = time.perf_counter()
    if b.comp.kind == "mesh":
        b.params, b.state = jax.device_put(
            (b.params, b.state), NamedSharding(b.comp.mesh, P()))
        b.exe = _round_exe(b.comp, b.params, b.state, b.epoch)
        b.step = b.comp.step
    else:
        precision = matmul_precision(files)
        with (contextlib.nullcontext() if precision == "default"
              else jax.default_matmul_precision(precision)):
            b.exe = b.comp.step.lower(b.params, b.state,
                                      jnp.asarray(b.epoch),
                                      *b.data).compile()
        b.step = b.exe
    b.compile_s = time.perf_counter() - t
    return b


def node_chips(b):
    """The chip each node's rows sit on (``None`` on one chip): partition
    ``p`` trains on chip ``p % m``."""
    if b.comp.kind != "mesh" or b.comp.m == 1:
        return None
    import numpy as np

    return np.asarray(b.comp.prog.part % b.comp.m, np.int32)


def first_steps(b) -> dict:
    """Drive the compiled step through its first three epochs; returns
    what the check compares: the three losses, the first gradient (from
    the optimizer's first moment) and the parameters after three
    updates."""
    import jax
    import jax.numpy as jnp

    prog = {"losses": []}
    for k in range(3):
        b.params, b.state, loss = b.step(b.params, b.state,
                                         jnp.asarray(b.epoch), *b.data)
        b.epoch += 1
        prog["losses"].append(float(loss))
        if k == 0:
            prog["grad"] = jax.tree.map(lambda m: m / (1.0 - b.opt.b1),
                                        _host(b.state["m"]))
    prog["params3"] = _host(b.params)
    return prog


def reference_run(files: dict, graph: dict, params0,
                  control: bool = False, chips=None) -> dict:
    """The plain reference's first three steps from the same start, in
    the configuration's float32 at its matmul precision; with
    ``control``, in bfloat16, the nearest precision below.  ``chips``
    (the chip of each node, on a mesh) adds the norms of each chip's
    share of the first weight gradient, for ``grad_scale_gap``."""
    import reference

    ref = {}
    ref["losses"], ref["grad"], ref["params3"] = reference.train3(
        params0, graph, files["config"]["optimizer"],
        "bfloat16" if control else "float32",
        "default" if control else matmul_precision(files))
    if chips is not None:
        ref["chip_grad_norms"] = reference.chip_grad_norms(
            params0, graph, chips, files["cell"]["chips"],
            matmul_precision(files))
    return ref


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t0: float, require_tpu: bool = True, config_override=None,
        log=print) -> dict:
    """One run of one cell; returns the result object."""
    import jax
    import jax.numpy as jnp

    import verdict

    t_jax = time.perf_counter()
    spec = load_spec()
    files = cell_files(spec, cell_name)
    if config_override is not None:
        files["config"] = config_override(files["config"])
    chips = files["cell"]["chips"]
    devs = check_devices(chips, require_tpu)
    dev = devs[0]
    where = f"[{dev.platform} {dev.device_kind} x{len(devs)}]"

    b = build(files, seed)
    mem = b.exe.memory_analysis()
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    hlo_text = b.exe.as_text()
    chip_of = node_chips(b)
    prog = first_steps(b)
    setup_s = time.perf_counter() - t0
    log(f"setup {where}: setup_s={setup_s:.3f} import_s={t_jax - t0:.3f} "
        f"inputs_s={b.inputs_s:.3f} compile_s={b.compile_s:.3f} "
        f"step_bytes={step_bytes} (arguments {mem.argument_size_in_bytes}, "
        f"outputs {mem.output_size_in_bytes}, temporaries "
        f"{mem.temp_size_in_bytes}, aliased {mem.alias_size_in_bytes})")

    # the window
    step, params, state, data, e = (b.step, b.params, b.state, b.data,
                                    b.epoch)
    comp = b.comp
    del b.exe, b.step, b.params, b.state, b.data, b.comp
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    losses = []
    with contextlib.ExitStack() as stack:
        if trace:
            jax.profiler.start_trace(tmp)
            stack.callback(jax.profiler.stop_trace)
        with jax.profiler.TraceAnnotation("bench.window"):
            start = time.perf_counter()
            prev = None
            while True:
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    params, state, loss = step(params, state,
                                               jnp.asarray(e), *data)
                e += 1
                losses.append(loss)
                if prev is not None:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        prev.block_until_ready()
                prev = loss
                if time.perf_counter() - start >= seconds:
                    break
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready((params, state, loss))
            wall = time.perf_counter() - start
    epochs = len(losses)
    failed = sum(not math.isfinite(float(v)) for v in losses)
    stats = [d.memory_stats() or {} for d in devs[:chips]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    extras = comp.result_extras()
    log(f"window {where}: epochs={epochs} wall_s={wall:.6f} "
        f"epoch_s={wall / epochs:.6f} step_hbm_gb={step_bytes / 1e9:.6f} "
        f"peak_bytes_in_use={peak}"
        + (f" plan {json.dumps(extras)}" if extras else ""))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": epochs, "failed": failed}
    if trace:
        try:
            result.update(_per_layer(spec, files, cell_name, tmp, hlo_text,
                                     epochs, wall, dev, b.g, b.cfg, extras,
                                     device, log, where))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    else:
        values = {"epoch_s": wall / epochs, "step_hbm_gb": step_bytes / 1e9,
                  "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(spec, cell_name, "end_to_end")}
        result["device"] = device

    # the check: the program's state is freed before the reference runs
    del params, state, step, comp, data, losses, loss, prev
    t_ref = time.perf_counter()
    ref = reference_run(files, b.graph, b.params0, chips=chip_of)
    read = verdict.readings(prog, ref, b.params0,
                            files["traffic"]["rp_ratio"] or 0)
    ok, checks = verdict.judge(read, files["limits"])
    ok = ok and failed == 0
    log(f"check {where}: reference_s={time.perf_counter() - t_ref:.3f} "
        f"program losses {prog['losses']} reference losses "
        f"{ref['losses']}")
    log(f"check {where}: readings " + json.dumps(read))
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    result["correct"] = bool(ok)
    result["checks"] = checks
    return result


def _per_layer(spec, files, cell_name, tmp, hlo_text, epochs, wall, dev, g,
               cfg, extras, device, log, where) -> dict:
    """The per-layer metrics of a traced window."""
    import glob
    import types

    import counts
    import graphgen
    import peaks as peakmod
    import tracecut

    paths = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {tmp}")
    events = tracecut.load_xplane(paths[0])
    # the window's bounds on the trace's clock: the host span around it
    spans = [(s, s + d) for n, s, d in events["host"] if n == "bench.window"]
    if not spans:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = spans[0]
    win = tracecut.reduce_window(events, lo, hi,
                                 tracecut.module_name(hlo_text))
    ops = tracecut.hlo_ops(hlo_text,
                           files["config"]["graph"]["directed_edges"])
    ctx = types.SimpleNamespace(
        cell=files["cell"], config=files["config"], traffic=files["traffic"],
        peaks=peakmod.chip_peaks(dev.device_kind), epochs=epochs,
        wall_s=wall, window=win, ops=ops, graph=g, gnn_cfg=cfg,
        hlo_text=hlo_text, extras=extras,
        model_flops=counts.model_flops(
            files["config"]["graph"]["n_nodes"],
            graphgen.layer_dims(files["config"])))
    metrics = {}
    for m in metrics_of(spec, cell_name, "per_layer"):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"trace {where}: window_s={win.window_s:.6f} busy_s={win.busy_s:.6f} "
        + " ".join(f"{k}={v['value']!r}" for k, v in metrics.items()))
    device = dict(device, busy_s=win.busy_s, window_s=win.window_s)
    return {"metrics": metrics, "device": device,
            "breakdown": {"device_ops": win.top_ops(ops),
                          "idle_gaps": [[w, s] for s, w in win.gaps[:10]]}}
