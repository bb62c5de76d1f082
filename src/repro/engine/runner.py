"""The engine's epoch driver: ``run(g, cfg, plan)`` is the single entry
point behind ``train_gnn``, ``train_gnn_batched``, ``launch.train
--graph-batches``, and the GNN benchmarks.

The loop is policy-free by construction: it asks the compiled plan for
its epoch data, calls the ONE jitted step, and services the autoprec
refresh as a plan-recompile hook.  Everything policy-shaped lives in the
plan and its compiler.

Observability (the plan's :class:`~repro.obs.policy.ObsPolicy`) wraps
the loop from the outside: spans around plan compile / epochs (split
into ``epoch/data``, the plan's epoch inputs, and ``epoch/dispatch``,
the counter's transfer and the step call) / autoprec re-solves, a
recompile counter, and the opt-in quant-health probe on its epoch
cadence.  Inside a ``jax.profiler`` trace the spans land on the host
plane beside the device's ops.  All of it is host-side or a separate
jitted pass — the training step's jaxpr is untouched, so obs-on runs
are bit-identical to obs-off (gated in ``tests/test_obs.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.engine import seeds
from repro.engine.compile import compile_plan
from repro.engine.plan import ExecutionPlan
from repro.engine.precision import AutoprecController
from repro.graph.models import gnn_forward, graph_tuple, init_gnn_params
from repro.obs.session import ObsSession
from repro.obs.trace import stopwatch
from repro.optim import AdamWConfig, adamw_init


def _accuracy(params, graph, labels, mask, cfg):
    logits = gnn_forward(params, graph, cfg, seed=0)
    correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    return jnp.sum(correct * mask) / jnp.maximum(mask.sum(), 1)


def _result(eval_fn, params, g, gt, history, n_epochs, dt, **extra):
    """Final full-graph val/test metrics + the shared engine result dict
    (every plan reports through this one contract)."""
    val = float(eval_fn(params, gt, g.labels, g.val_mask.astype(jnp.float32)))
    test = float(eval_fn(params, gt, g.labels,
                         g.test_mask.astype(jnp.float32)))
    return {"test_acc": test, "val_acc": val, "history": history,
            "epochs_per_sec": n_epochs / dt, "params": params, **extra}


def _probe_graph(compiled, gt):
    """The graph tuple the quant-health probe runs on: the plan's
    calibration unit (one padded batch for partition plans, the full
    graph otherwise — mesh plans have no calibration unit and probe the
    full graph, which is a measurement pass, not a training stash)."""
    try:
        cal_gt, _, _, _ = compiled.calibration()
        return cal_gt
    except ValueError:
        return gt


def run(g, cfg, plan: ExecutionPlan | None = None, opt=None, *,
        n_epochs: int = 100, seed: int = 0, eval_every: int = 10,
        verbose: bool = False, batches=None, mesh=None) -> dict:
    """Train ``cfg`` on ``g`` under ``plan``; returns the engine result
    dict (``test_acc``, ``val_acc``, ``history``, ``epochs_per_sec``,
    ``params``, ``cfg``, ``plan``, plus the partition extras
    ``n_parts`` / ``updates_per_epoch`` / ``batch_nodes`` /
    ``batch_edges``, the autoprec extras ``bits_per_layer`` /
    ``bit_budget_bytes``, and — when the plan's obs policy is enabled —
    the live :class:`~repro.obs.session.ObsSession` under ``"obs"``).

    ``batches`` / ``mesh`` are runtime resources for partition plans
    (prebuilt sampling pass, device mesh) — see
    :func:`repro.engine.compile.compile_plan`.
    """
    plan = plan if plan is not None else ExecutionPlan()
    if (plan.precision.kind == "autoprec"
            and plan.precision.calibration == "obs"
            and not (plan.obs.enabled and plan.obs.quant_stats)):
        raise ValueError("precision.calibration='obs' sources sensitivities "
                         "from the quant-health telemetry channel; the plan "
                         "needs obs=ObsPolicy(enabled=True, "
                         "quant_stats=True)")
    obs = ObsSession.from_policy(plan.obs)
    opt = opt or AdamWConfig(lr=5e-3, weight_decay=0.0)
    cfg = plan.kernel.apply(cfg)
    key = jax.random.PRNGKey(seed)
    params = init_gnn_params(key, cfg, g.n_feats)
    state = adamw_init(params, opt)
    with obs.activate():
        with obs.span("plan/compile", plan=plan.describe()):
            compiled = compile_plan(g, cfg, plan, opt, batches=batches,
                                    mesh=mesh, seed=seed, obs=obs)
        ctrl = None
        if plan.precision.kind == "autoprec":
            cal_gt, cal_labels, cal_mask, cal_nm = compiled.calibration()
            ctrl = AutoprecController(cal_gt, cal_labels, cal_mask, cfg,
                                      plan.precision.bit_budget,
                                      plan.precision.refresh, seed,
                                      node_mask=cal_nm,
                                      calibration=plan.precision.calibration)
            with obs.span("autoprec/solve", epoch=0):
                cfg, _ = ctrl.allocate(params)
            with obs.span("plan/recompile", epoch=0):
                compiled = compiled.recompile(cfg)
            obs.counter("engine/recompiles").inc()
        eval_fn = jax.jit(partial(_accuracy, cfg=cfg))
        gt = graph_tuple(g)
        order_rng = seeds.order_rng(seed)
        history = []
        with stopwatch("train/epochs", epochs=n_epochs) as sw:
            for epoch in range(n_epochs):
                if ctrl is not None and ctrl.due(epoch):
                    with obs.span("autoprec/solve", epoch=epoch):
                        cfg, changed = ctrl.allocate(params)
                    if changed:
                        with obs.span("plan/recompile", epoch=epoch):
                            compiled = compiled.recompile(cfg)
                        obs.counter("engine/recompiles").inc()
                with obs.span("epoch", epoch=epoch):
                    with obs.span("epoch/data"):
                        data = compiled.epoch_data(order_rng)
                    with obs.span("epoch/dispatch"):
                        params, state, loss = compiled.step(
                            params, state, jnp.asarray(epoch), *data)
                if obs.quant_due(epoch):
                    with obs.span("obs/quant_probe", epoch=epoch):
                        obs.quant_probe(params, _probe_graph(compiled, gt),
                                        epoch, cfg)
                if verbose and (epoch % eval_every == 0
                                or epoch == n_epochs - 1):
                    va = eval_fn(params, gt, g.labels,
                                 g.val_mask.astype(jnp.float32))
                    history.append((epoch, float(loss), float(va)))
            jax.block_until_ready(params)
    extra = ctrl.extras() if ctrl is not None else {}
    extra.update(compiled.result_extras())
    extra["cfg"] = cfg
    extra["plan"] = plan
    if obs.enabled:
        extra["obs"] = obs
    return _result(eval_fn, params, g, gt, history, n_epochs, sw.elapsed_s,
                   **extra)
