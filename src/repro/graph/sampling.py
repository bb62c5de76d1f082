"""Cluster-GCN-style partition sampling: node partitions → padded subgraph
batches with static shapes.

Full-graph training materializes every layer's stash for all N nodes at
once; the paper's block-wise compression shrinks those bytes but cannot
change the O(N) live set.  Mini-batch subgraph training does: partition the
nodes (METIS-free — balanced random or greedy multi-source BFS for
locality), train on one intra-partition subgraph at a time, and only that
partition's activations are ever live.  Each batch runs the exact same
compressed ``custom_vjp`` stack as the full graph.

jit stability: every batch in one call is padded to the *same* static
node/edge counts (max over partitions, rounded up to a bucket multiple), so
``lax.scan`` over stacked batches traces once and ``spmm`` segment-sums /
``compressed_matmul`` stashes never see ragged shapes.  Padding is inert by
construction: pad feature rows are zero, pad edges carry weight 0 and point
at node 0, and pad nodes are excluded from every loss/metric mask — see
``tests/test_gnn_batched.py`` for the zero-gradient proof.

``halo=k`` additionally includes the k-hop in-neighborhood of each
partition (Cluster-GCN's boundary-edge recovery): halo nodes participate in
aggregation but carry no loss (their train/val/test masks are zeroed).
"""
from __future__ import annotations

import collections
import dataclasses
import math

import numpy as np
import jax
import jax.numpy as jnp

from repro.graph.adjacency import EdgeList
from repro.graph.data import Graph, in_adjacency


# ------------------------------------------------------------ partitioners
def random_partition(n_nodes: int, n_parts: int, seed: int = 0) -> np.ndarray:
    """Balanced uniform-random node partition: (N,) int part ids, sizes
    differing by at most 1."""
    if not 1 <= n_parts <= n_nodes:
        raise ValueError(f"n_parts={n_parts} must be in [1, {n_nodes}]")
    rng = np.random.default_rng(seed)
    base, extra = divmod(n_nodes, n_parts)
    counts = base + (np.arange(n_parts) < extra)
    part = np.repeat(np.arange(n_parts), counts)
    rng.shuffle(part)
    return part


def bfs_partition(edge_src, edge_dst, n_nodes: int, n_parts: int,
                  seed: int = 0) -> np.ndarray:
    """Greedy multi-source BFS partition (METIS-free locality).

    Grow all parts simultaneously from random seed nodes, always expanding
    the currently-smallest part, each capped at ceil(N/P) nodes; nodes
    unreached by any frontier (disconnected shards) fill the smallest parts.
    Keeps most edges intra-partition on homophilous graphs, which is what
    limits Cluster-GCN's gradient bias.
    """
    if not 1 <= n_parts <= n_nodes:
        raise ValueError(f"n_parts={n_parts} must be in [1, {n_nodes}]")
    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    nbr, starts = in_adjacency(src, dst, n_nodes)
    rng = np.random.default_rng(seed)
    cap = math.ceil(n_nodes / n_parts)
    part = np.full(n_nodes, -1, np.int64)
    sizes = np.zeros(n_parts, np.int64)
    seeds = rng.choice(n_nodes, n_parts, replace=False)
    queues = []
    for p, s in enumerate(seeds):
        part[s] = p
        sizes[p] = 1
        queues.append(collections.deque([int(s)]))
    active = set(range(n_parts))
    while active:
        p = min(active, key=lambda q: sizes[q])
        if not queues[p] or sizes[p] >= cap:
            active.discard(p)
            continue
        u = queues[p].popleft()
        for v in nbr[starts[u]:starts[u + 1]]:
            if part[v] < 0 and sizes[p] < cap:
                part[v] = p
                sizes[p] += 1
                queues[p].append(int(v))
    for v in np.flatnonzero(part < 0):
        p = int(np.argmin(sizes))
        part[v] = p
        sizes[p] += 1
    return part


# ------------------------------------------------------------ batch pytree
_FIELDS = ("features", "labels", "edge_src", "edge_dst", "gcn_weight",
           "mean_weight", "train_mask", "val_mask", "test_mask",
           "node_mask", "n_real_nodes", "n_real_edges")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SubgraphBatch:
    """One padded node-partition subgraph.

    Every field is an array leaf (the real counts included, as scalars) so
    batches stack along a leading axis for ``lax.scan`` epochs and
    data-parallel device sharding.  Local node order is: owned partition
    nodes, then halo nodes, then zero padding; ``node_mask`` marks real
    (owned + halo) rows, while train/val/test masks cover owned rows only.
    """
    features: jnp.ndarray      # (Np, F) f32 — zero on padding rows
    labels: jnp.ndarray        # (Np,) i32 — 0 on padding
    edge_src: jnp.ndarray      # (Ep,) i32 — 0 on padding
    edge_dst: jnp.ndarray      # (Ep,) i32 — 0 on padding
    gcn_weight: jnp.ndarray    # (Ep,) f32 — 0 on padding edges
    mean_weight: jnp.ndarray   # (Ep,) f32 — 0 on padding edges
    train_mask: jnp.ndarray    # (Np,) f32 — owned nodes only
    val_mask: jnp.ndarray      # (Np,) f32
    test_mask: jnp.ndarray     # (Np,) f32
    node_mask: jnp.ndarray     # (Np,) f32 — 1 real (incl. halo), 0 padding
    n_real_nodes: jnp.ndarray  # () i32
    n_real_edges: jnp.ndarray  # () i32

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in _FIELDS), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_nodes(self) -> int:
        """Padded (static) node count."""
        return int(self.features.shape[0])

    @property
    def n_edges(self) -> int:
        """Padded (static) edge count."""
        return int(self.edge_src.shape[0])

    def graph_tuple(self):
        """The ``(features, gcn_adj, mean_adj)`` that
        :func:`repro.graph.models.gnn_forward` consumes: edge lists, as
        the batch's edges are traced under the epoch's ``lax.scan``."""
        n = self.n_nodes
        return (self.features,
                EdgeList(self.edge_src, self.edge_dst, self.gcn_weight, n),
                EdgeList(self.edge_src, self.edge_dst, self.mean_weight, n))


def stack_batches(batches: list[SubgraphBatch]) -> SubgraphBatch:
    """Stack same-shape batches into one pytree with a leading batch axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


def group_batches(stacked: SubgraphBatch, order, n_updates: int,
                  grad_accum: int, dp: int) -> SubgraphBatch:
    """Reorder stacked batches and reshape every leaf to the epoch scan's
    update-group layout ``(n_updates, grad_accum, dp, ...)`` — the data
    contract of the engine's partition lowering
    (:class:`repro.engine.compile._CompiledPartition`)."""
    return jax.tree.map(
        lambda x: x[order].reshape(n_updates, grad_accum, dp, *x.shape[1:]),
        stacked)


# ---------------------------------------------------------------- sampler
def _bucket(n: int, multiple: int) -> int:
    return max(multiple, ((n + multiple - 1) // multiple) * multiple)


def make_subgraph_batches(g: Graph, n_parts: int, *, method: str = "bfs",
                          halo: int = 0, seed: int = 0,
                          node_multiple: int = 64, edge_multiple: int = 256,
                          renormalize: bool = False) -> list[SubgraphBatch]:
    """Split ``g`` into ``n_parts`` padded subgraph batches.

    method        "bfs" (greedy multi-source BFS, locality-preserving) or
                  "random" (balanced uniform — Cluster-GCN's stochastic
                  partition baseline).
    halo          hops of in-neighborhood context added around each
                  partition (0 = pure intra-partition edges).
    node/edge_multiple
                  pad buckets: all batches share one static (node, edge)
                  shape, the max real size rounded up to these multiples
                  (1 = tight padding; n_parts=1 with multiples of 1
                  reproduces the full graph exactly).
    renormalize   recompute GCN/mean aggregation weights from *subgraph*
                  degrees (Cluster-GCN's Â normalization) instead of
                  slicing the full-graph weights.  Off by default so
                  n_parts=1 matches full-graph training bit-for-bit.
    """
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.edge_dst)
    n = g.n_nodes
    if n_parts == 1:
        part = np.zeros(n, np.int64)
    elif method == "random":
        part = random_partition(n, n_parts, seed)
    elif method == "bfs":
        part = bfs_partition(src, dst, n, n_parts, seed)
    else:
        raise ValueError(f"unknown partition method {method!r}")

    feats = np.asarray(g.features)
    labels = np.asarray(g.labels)
    gcn_w = np.asarray(g.gcn_weight)
    mean_w = np.asarray(g.mean_weight)
    masks = {"train": np.asarray(g.train_mask), "val": np.asarray(g.val_mask),
             "test": np.asarray(g.test_mask)}

    raw = []
    for p in range(n_parts):
        owned = np.flatnonzero(part == p)
        in_set = np.zeros(n, bool)
        in_set[owned] = True
        for _ in range(halo):
            in_set[src[in_set[dst]]] = True
        halo_nodes = np.setdiff1d(np.flatnonzero(in_set), owned,
                                  assume_unique=True)
        nodes = np.concatenate([owned, halo_nodes])
        loc = np.full(n, -1, np.int64)
        loc[nodes] = np.arange(len(nodes))
        keep = in_set[src] & in_set[dst]
        s_loc, d_loc = loc[src[keep]], loc[dst[keep]]
        if renormalize:
            deg = np.bincount(d_loc, minlength=len(nodes)).astype(np.float64)
            deg = np.maximum(deg, 1.0)
            gw = 1.0 / np.sqrt(deg[s_loc] * deg[d_loc])
            mw = 1.0 / deg[d_loc]
        else:
            gw, mw = gcn_w[keep], mean_w[keep]
        raw.append((nodes, len(owned), s_loc, d_loc, gw, mw))

    n_pad = _bucket(max(len(r[0]) for r in raw), node_multiple)
    e_pad = _bucket(max(len(r[2]) for r in raw), edge_multiple)

    batches = []
    for nodes, n_owned, s_loc, d_loc, gw, mw in raw:
        nl, el = len(nodes), len(s_loc)
        f = np.zeros((n_pad, feats.shape[1]), np.float32)
        f[:nl] = feats[nodes]
        lab = np.zeros(n_pad, np.int32)
        lab[:nl] = labels[nodes]
        es = np.zeros(e_pad, np.int32)
        ed = np.zeros(e_pad, np.int32)
        ew_g = np.zeros(e_pad, np.float32)
        ew_m = np.zeros(e_pad, np.float32)
        es[:el], ed[:el] = s_loc, d_loc
        ew_g[:el], ew_m[:el] = gw, mw
        node_mask = np.zeros(n_pad, np.float32)
        node_mask[:nl] = 1.0
        owned_rows = np.arange(n_pad) < n_owned
        m = {}
        for k, full in masks.items():
            mk = np.zeros(n_pad, np.float32)
            mk[:nl] = full[nodes].astype(np.float32)
            m[k] = mk * owned_rows
        batches.append(SubgraphBatch(
            features=jnp.asarray(f), labels=jnp.asarray(lab),
            edge_src=jnp.asarray(es), edge_dst=jnp.asarray(ed),
            gcn_weight=jnp.asarray(ew_g), mean_weight=jnp.asarray(ew_m),
            train_mask=jnp.asarray(m["train"]), val_mask=jnp.asarray(m["val"]),
            test_mask=jnp.asarray(m["test"]),
            node_mask=jnp.asarray(node_mask),
            n_real_nodes=jnp.asarray(nl, jnp.int32),
            n_real_edges=jnp.asarray(el, jnp.int32)))
    return batches
