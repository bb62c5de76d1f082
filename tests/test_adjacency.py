"""The padded-row aggregation layout (``repro.graph.adjacency``).

A full graph aggregates through a destination-sorted padded-row layout
built once on the host; partition batches and the mesh keep the edge
list.  On the CPU both spellings add each node's rounded terms in
edge-list order from zero, so everything here is compared bit for bit.
"""
from __future__ import annotations

import dataclasses
import re
from unittest import mock

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.compressor import CompressionConfig
from repro.engine import run, seeds
from repro.engine.compile import compile_plan, engine_loss
from repro.engine.forward import TENSOR_STASH, plan_gnn_stashes
from repro.engine.plan import ExecutionPlan, ObsPolicy, SamplingPolicy
from repro.graph import GNNConfig, cora_like
from repro.graph import adjacency
from repro.graph.adjacency import EdgeList, padded_rows, slots_per_row
from repro.graph.models import graph_tuple, init_gnn_params
from repro.graph.train import _loss_fn
from repro.obs.scopes import scope_of
from repro.optim import AdamWConfig, adamw_init


def _random_edges(rng, n, e):
    src = rng.integers(0, n, e)
    dst = (n * rng.random(e) ** 2).astype(np.int64)
    return src, dst


def _case(name):
    """``(src, dst, weights, n_nodes)`` of one test graph."""
    rng = np.random.default_rng(7)
    if name == "cora":
        g = cora_like(0.25, seed=1)
        return (np.asarray(g.edge_src), np.asarray(g.edge_dst),
                [np.asarray(g.gcn_weight), np.asarray(g.mean_weight)],
                g.n_nodes)
    if name == "hub":
        # median in-degree 6 (K = 4) and one hub with 200 > K * K edges
        n = 300
        src, dst = _random_edges(rng, n, 6 * n)
        dst = np.concatenate([dst, np.full(200, 5)])
        src = np.concatenate([src, rng.integers(0, n, 200)])
    elif name == "isolated":
        # nodes 0..9 receive no edge at all
        n = 120
        src, dst = _random_edges(rng, n, 700)
        dst = np.where(dst < 10, dst + 10, dst)
    else:  # "tiny": one node, three edges (K is forced to 8 below)
        n, src, dst = 1, np.zeros(3, np.int64), np.zeros(3, np.int64)
    deg = np.bincount(dst, minlength=n).astype(np.float64)
    mean = (1.0 / deg[dst]).astype(np.float32)
    other = rng.normal(size=dst.size).astype(np.float32)
    return src, dst, [other, mean], n


CASES = ["cora", "hub", "isolated", "tiny"]


@pytest.fixture(params=["levels-and-tail", "levels-only"])
def tail_rows(request, monkeypatch):
    """With the default threshold the hubs' last edges go to the tail;
    with 0 every edge is in a level."""
    if request.param == "levels-only":
        monkeypatch.setattr(adjacency, "TAIL_SHARE", 0.0)
    return request.param


def _build(name):
    src, dst, ws, n = _case(name)
    if name == "tiny":
        # K never exceeds the median degree, so fewer edges than slots in
        # a row needs K forced
        with mock.patch.object(adjacency, "slots_per_row", lambda deg: 8):
            return src, dst, ws, n, padded_rows(src, dst, ws, n)
    return src, dst, ws, n, padded_rows(src, dst, ws, n)


def _slots(plan):
    """Each node's terms in the order the fold adds them:
    ``{node: [(src, w), ...]}``, and every padding slot's ``(idx, w)``."""
    row_node = np.asarray(plan.row_node)
    per_node, padding = {}, []
    for lv in plan.levels:
        start, idx, w, scale = (np.asarray(a) for a in
                                (lv.start, lv.idx, lv.w, lv.scale))
        for s in range(start.shape[0]):
            for k in range(idx.shape[1]):
                for b in range(idx.shape[2]):
                    if scale[s, k, b]:
                        node = int(row_node[start[s] + b])
                        per_node.setdefault(node, []).append(
                            (int(idx[s, k, b]), float(w[s, k, b])))
                    else:
                        padding.append((int(idx[s, k, b]),
                                        float(w[s, k, b])))
    rows = np.asarray(plan.tail_row)
    assert (np.diff(rows) >= 0).all()
    for r, i, w in zip(rows, np.asarray(plan.tail_src),
                       np.asarray(plan.tail_w)):
        if r == plan.n_rows:    # bucket padding, dropped by the scatter
            padding.append((int(i), float(w)))
        else:
            per_node.setdefault(int(row_node[r]), []).append(
                (int(i), float(w)))
    return per_node, padding


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_every_edge_lands_in_one_slot_in_edge_order(name, direction,
                                                    tail_rows):
    src, dst, ws, n, rows = _build(name)
    if direction == "bwd":
        src, dst = dst, src
    for w, adj in zip(ws, rows):
        per_node, padding = _slots(getattr(adj, direction))
        want = {}
        for e in range(dst.size):
            want.setdefault(int(dst[e]), []).append((int(src[e]),
                                                     float(w[e])))
        assert per_node == want
        assert all(p == (0, 0.0) for p in padding)


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("transposed", [False, True])
def test_layout_equals_the_edge_list(name, transposed, tail_rows):
    src, dst, ws, n, rows = _build(name)
    h = jax.random.normal(jax.random.PRNGKey(0), (n, 19))
    for w, adj in zip(ws, rows):
        el = EdgeList(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w), n)
        if transposed:
            adj, el = adj.T, el.T
        got = jax.jit(lambda h, a: a.aggregate(h))(h, adj)
        want = jax.jit(lambda h, a: a.aggregate(h))(h, el)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        x = jax.random.normal(jax.random.PRNGKey(1), (n, 19))
        got = jax.jit(lambda x, h, a: a.add_aggregate(x, h))(x, h, adj)
        want = jax.jit(lambda x, h, a: a.add_aggregate(x, h))(x, h, el)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", ["hub", "isolated"])
def test_padding_slots_add_exactly_nothing(name, tail_rows):
    """A non-finite source row reaches exactly its out-neighbours: the
    padding slots that point at it (every padding slot reads row 0) add
    nothing, not ``0 * inf``."""
    src, dst, ws, n, rows = _build(name)
    h = np.array(jax.random.normal(jax.random.PRNGKey(2), (n, 5)))
    h[0] = np.inf
    h[1] = np.nan
    for w, adj in zip(ws, rows):
        got = np.asarray(jax.jit(lambda h, a: a.aggregate(h))(h, adj))
        reached = np.zeros(n, bool)
        reached[dst[(src == 0) | (src == 1)]] = True
        assert np.isfinite(got[~reached]).all()
        el = EdgeList(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w), n)
        np.testing.assert_array_equal(got, np.asarray(el.aggregate(h)))
        assert {i for i, _ in _slots(adj.fwd)[1]} <= {0}


@pytest.mark.parametrize("high", [7, 1 << 16, 1 << 20, 1 << 33])
def test_stable_order_is_a_stable_argsort(high):
    keys = np.random.default_rng(high % 97).integers(0, high, 5000)
    np.testing.assert_array_equal(adjacency._stable_order(keys),
                                  np.argsort(keys, kind="stable"))


def test_build_is_deterministic():
    a = _build("hub")[-1]
    b = _build("hub")[-1]
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("degrees, k", [([14] * 5, 8), ([11, 11, 12], 8),
                                        ([16] * 3, 16), ([17] * 3, 16),
                                        ([7, 7, 7], 4), ([1, 1, 9], 1),
                                        ([0, 0, 3], 1), ([], 1)])
def test_slots_per_row_is_the_power_of_two_under_the_median(degrees, k):
    assert slots_per_row(np.asarray(degrees, np.int64)) == k


@pytest.mark.parametrize("name", ["cora", "hub"])
@pytest.mark.parametrize("op", ["aggregate", "concat_aggregate"])
def test_vjp_equals_autodiff_of_the_edge_list(name, op, tail_rows):
    """The layout's custom VJP (the transposed layout) gives the
    gradient autodiff takes through the edge list's gather and
    segment-sum."""
    src, dst, ws, n, rows = _build(name)
    h = jax.random.normal(jax.random.PRNGKey(3), (n, 11))
    for w, adj in zip(ws, rows):
        el = EdgeList(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w), n)
        c = jax.random.normal(jax.random.PRNGKey(4),
                              jax.eval_shape(getattr(el, op), h).shape)

        def grad(a):
            return jax.jit(jax.grad(
                lambda h: jnp.sum(getattr(a, op)(h) * c)))(h)

        np.testing.assert_array_equal(np.asarray(grad(adj)),
                                      np.asarray(grad(el)))


@pytest.fixture(scope="module")
def g():
    return cora_like(0.25, seed=0)


@pytest.mark.parametrize("arch", ["gcn", "sage"])
def test_engine_grads_equal_per_op_autodiff_on_the_layout(g, arch):
    """GCN aggregates after the linear with the GCN weights; no benchmark
    cell runs it, so its engine-vs-autodiff parity is held here."""
    cfg = GNNConfig(arch=arch, hidden=(32,), n_classes=g.num_classes,
                    compression=CompressionConfig(bits=2, group_size=64,
                                                  rp_ratio=8))
    params = init_gnn_params(jax.random.PRNGKey(1), cfg, g.n_feats)
    gt = graph_tuple(g)
    mask = g.train_mask.astype(jnp.float32)
    seed = seeds.sr_seed(4)
    per_op = jax.jit(jax.grad(_loss_fn), static_argnums=(4,))(
        params, gt, g.labels, mask, cfg, seed)
    engine = jax.jit(jax.grad(engine_loss), static_argnums=(4, 7, 8))(
        params, gt, g.labels, mask, cfg, seed, None,
        plan_gnn_stashes(cfg, g.n_feats, g.n_nodes), TENSOR_STASH)
    for a, b in zip(jax.tree.leaves(per_op), jax.tree.leaves(engine)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_full_graph_step_has_no_edge_scatter_and_keeps_its_scopes(g):
    """The compiled full-graph step holds no sort and no scatter with a
    row per directed edge, and its aggregation scopes hold the layout's
    gathers."""
    cfg = GNNConfig(hidden=(32,), n_classes=g.num_classes,
                    compression=CompressionConfig(bits=2, group_size=64,
                                                  rp_ratio=8))
    opt = AdamWConfig(lr=5e-3, weight_decay=0.0)
    comp = compile_plan(g, cfg, ExecutionPlan(), opt)
    params = init_gnn_params(jax.random.PRNGKey(0), cfg, g.n_feats)
    state = adamw_init(params, opt)
    text = comp.step.lower(params, state, jnp.asarray(0),
                           *comp.epoch_data(None)).compile().as_text()
    lines = text.splitlines()
    assert not [ln for ln in lines if re.search(r"\bsort\(", ln)]
    edge_rows = re.compile(rf"\[{g.n_edges}[,\]]")
    assert not [ln for ln in lines
                if re.search(r"\bscatter\(", ln) and edge_rows.search(ln)]
    gathered_in = {scope_of(m.group(1)) for ln in lines
                   if re.search(r"\bgather\(", ln)
                   for m in [re.search(r'op_name="([^"]*)"', ln)] if m}
    assert {"agg_fwd", "agg_bwd"} <= gathered_in


def _counted(g, plan, n_epochs=3):
    cfg = GNNConfig(hidden=(16,), n_classes=g.num_classes)
    plan = dataclasses.replace(plan, obs=ObsPolicy(enabled=True))
    r = run(g, cfg, plan, n_epochs=n_epochs, seed=0)
    return r["obs"].registry.snapshot()


def test_layout_is_built_once_per_graph():
    g = cora_like(0.2, seed=3)   # a graph no other test has laid out
    snap = _counted(g, ExecutionPlan(), n_epochs=4)
    assert snap["graph/agg_layout_builds"] == 1
    assert snap["graph/agg_slot_share"] >= 1.0
    assert snap["engine/agg_route/layout"] > 0
    assert "engine/agg_route/edges" not in snap
    # a second run over the same graph reuses its layout
    snap = _counted(g, ExecutionPlan(), n_epochs=2)
    assert "graph/agg_layout_builds" not in snap


def test_partition_lowering_routes_edge_lists(g):
    plan = ExecutionPlan(sampling=SamplingPolicy(kind="partition",
                                                 n_parts=2))
    snap = _counted(g, plan, n_epochs=2)
    assert snap["engine/agg_route/edges"] > 0
