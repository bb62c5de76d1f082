"""The aggregation operand: what ``spmm`` sums ``Â·h`` over.

Two spellings of one product, chosen by the operand's type:

* :class:`EdgeList` — edge arrays that are traced per step (stacked
  partition batches under ``lax.scan``, the mesh engine's halo-extended
  tables): gather ``h[src]``, weight, ``segment_sum`` over ``dst``.
* :class:`PaddedRows` — a static graph's destination-sorted padded-row
  layout, built once on the host (:func:`padded_rows`): each step
  gathers ``K`` source rows per destination row and adds them in, so
  neither a per-edge message tensor nor a scatter or sort of edge
  length is left on the device.

Both add a node's terms in edge-list order, one rounded product
``h[s] * w`` at a time, starting from zero — which is what a scatter-add
does on the CPU — so on the CPU the two spellings agree bit for bit and
full-graph training repeats the partition and mesh lowerings exactly.

Layout.  Nodes are ranked by in-degree, highest first (stable), and a
node's edges are cut into *levels* of ``K`` slots: level ``j`` holds
edges ``jK .. jK+K-1`` of every node with more than ``jK`` edges, which
are the first ``n_j`` nodes of the ranking.  So level ``j`` updates a
prefix of the accumulator and continues each node's running sum where
level ``j - 1`` left it.  A level runs as one ``lax.scan`` over steps of
``B`` rows, at most :data:`STEP_SLOTS` slots a step (that bounds the
gathered block a step keeps on the device).  Levels stop where fewer
than :data:`TAIL_SHARE` of the nodes are left; those hubs' remaining
edges (1.0% of ogbn-arxiv's) are added last by one scatter-add whose
rows are sorted, continuing each hub's sum in edge order.  ``K`` follows
from the graph: the largest power of two at or below the median
in-degree.  Row and tail counts are rounded up to buckets of the graph's
size, so a configuration compiles once whatever its draw.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.act_compress import zero_ct
from repro.obs.metrics import get_metrics

#: Slots one step of a big level gathers: a step's gathered block is
#: ``STEP_SLOTS × F`` floats (256 MiB at F = 256).
STEP_SLOTS = 1 << 18

#: Levels stop where fewer than this share of the nodes is left: a level
#: of a few hubs costs more in per-step work than its edges do as a
#: scatter, so the hubs' last edges follow as one sorted scatter-add.
TAIL_SHARE = 1 / 64

#: Shapes are rounded up to these shares of the node count (a level's
#: rows) and of the edge count (the tail), so that every graph drawn at
#: one size compiles to one program: a level's exact row count moves
#: with the draw by well under a bucket.
ROW_BUCKET, TAIL_BUCKET = 1 / 32, 1 / 256


def _dataclass(data, meta=()):
    def register(cls):
        cls = dataclasses.dataclass(frozen=True)(cls)
        return jax.tree_util.register_dataclass(
            cls, data_fields=list(data), meta_fields=list(meta))
    return register


# ------------------------------------------------------------- edge lists
@_dataclass(data=("src", "dst", "w"), meta=("n_nodes",))
class EdgeList:
    """``out[d] += w_e * h[s]`` over edge arrays; ``n_nodes`` output rows."""

    src: jnp.ndarray
    dst: jnp.ndarray
    w: jnp.ndarray
    n_nodes: int

    @property
    def T(self) -> "EdgeList":
        return EdgeList(self.dst, self.src, self.w, self.n_nodes)

    def aggregate(self, h):
        get_metrics().counter("engine/agg_route/edges").inc()
        msg = h[self.src] * self.w[:, None]
        return jax.ops.segment_sum(msg, self.dst, num_segments=self.n_nodes)

    def concat_aggregate(self, h):
        return jnp.concatenate([h, self.aggregate(h)], axis=1)

    def add_aggregate(self, x, h):
        # XLA folds the add into the scatter: each row's sum starts at x
        return x + self.aggregate(h)


# ------------------------------------------------------- padded-row layout
@_dataclass(data=("start", "idx", "w", "scale"))
class Level:
    """One level in ``S`` steps of ``B`` accumulator rows and ``K`` slots,
    run by one ``lax.scan``.  Slot ``[s, k, b]`` adds ``w · h[idx]`` to
    row ``start[s] + b``; ``scale`` is 1 on a slot that holds an edge and
    0 on padding (whose ``idx`` is 0 and ``w`` 0)."""

    start: jnp.ndarray   # (S,) i32
    idx: jnp.ndarray     # (S, K, B) i32
    w: jnp.ndarray       # (S, K, B) f32
    scale: jnp.ndarray   # (S, K, B) f32


@_dataclass(data=("rank_of", "row_node", "levels", "tail_row", "tail_src",
                  "tail_w"), meta=("n_rows",))
class RowPlan:
    """One direction of the layout with one set of edge weights.  The
    accumulator has ``n_rows`` rows in in-degree rank order; node ``u``'s
    sum ends in row ``rank_of[u]``, and row ``r`` belongs to node
    ``row_node[r]`` (node 0 for the padding rows past the last node).
    The edges the levels leave, those of the few hubs, follow as a
    scatter-add with sorted rows: ``tail_w · h[tail_src]`` into
    ``tail_row``, whose padding names row ``n_rows`` and is dropped."""

    rank_of: jnp.ndarray            # (N,) i32
    row_node: jnp.ndarray           # (n_rows,) i32
    levels: tuple[Level, ...]
    tail_row: jnp.ndarray           # (T,) i32, ascending
    tail_src: jnp.ndarray           # (T,) i32
    tail_w: jnp.ndarray             # (T,) f32
    n_rows: int


def _fold(h, plan: RowPlan, init=None):
    """``init + Â·h`` (``init`` zero when None): the accumulator starts
    at ``init``; each level's steps add their ``K`` slots one after
    another into their rows, and the tail's scatter-add comes last."""
    get_metrics().counter("engine/agg_route/layout").inc()
    acc = (jnp.zeros((plan.n_rows, h.shape[1]), h.dtype) if init is None
           else init[plan.row_node])
    for lv in plan.levels:
        rows = lv.idx.shape[-1]

        def step(acc, slots):
            start, idx, w, scale = slots
            a = lax.dynamic_slice_in_dim(acc, start, rows)
            g = h[idx]                                   # (K, B, F)
            zero = jnp.zeros_like(a)
            for k in range(idx.shape[0]):
                # the product is rounded before the add — the 0/1 scale
                # takes the place an add would otherwise fuse into (an
                # FMA on the CPU), so each term is the edge list's
                # ``h[s] * w``; the select keeps a padding slot's 0 from
                # meeting a non-finite row.  (lax, not jnp: a K-slot body
                # per level and direction, traced at every compile.)
                row = lambda t: lax.broadcast_in_dim(
                    lax.index_in_dim(t, k, keepdims=False), a.shape, (0,))
                wk, sk = row(w), row(scale)
                m = lax.mul(lax.mul(lax.index_in_dim(g, k, keepdims=False),
                                    wk), sk)
                a = lax.add(a, lax.select(lax.gt(sk, zero), m, zero))
            return lax.dynamic_update_slice_in_dim(acc, a, start, 0), None

        acc, _ = lax.scan(step, acc, (lv.start, lv.idx, lv.w, lv.scale))
    if plan.tail_row.shape[0]:
        msg = h[plan.tail_src] * plan.tail_w[:, None]
        acc = acc.at[plan.tail_row].add(msg, indices_are_sorted=True,
                                        mode="drop")
    return acc[plan.rank_of]


@jax.custom_vjp
def _rows_spmm(h, adj):
    return _fold(h, adj.fwd)


def _rows_spmm_fwd(h, adj):
    return _fold(h, adj.fwd), adj


def _rows_spmm_bwd(adj, g):
    # the transpose is the other direction's layout: the same function
    # the engine's backward calls on ``adj.T``
    return _fold(g, adj.bwd), zero_cotangent(adj)


_rows_spmm.defvjp(_rows_spmm_fwd, _rows_spmm_bwd)


def zero_cotangent(tree):
    """The cotangent of a graph constant: float0 zeros for integer
    leaves, zeros for the others."""
    return jax.tree.map(lambda x: zero_ct(x) if jnp.issubdtype(
        x.dtype, jnp.integer) else jnp.zeros_like(x), tree)


@jax.custom_vjp
def _rows_concat(h, adj):
    return jnp.concatenate([h, _fold(h, adj.fwd)], axis=1)


def _rows_concat_fwd(h, adj):
    return jnp.concatenate([h, _fold(h, adj.fwd)], axis=1), adj


def _rows_concat_bwd(adj, g):
    # h's two cotangents summed as an edge list's scatter sums them: the
    # transposed product adds into the direct one
    d = g.shape[1] // 2
    return _fold(g[:, d:], adj.bwd, init=g[:, :d]), zero_cotangent(adj)


_rows_concat.defvjp(_rows_concat_fwd, _rows_concat_bwd)


@_dataclass(data=("fwd", "bwd"))
class PaddedRows:
    """A static graph's aggregation: ``fwd`` sums into destinations,
    ``bwd`` (its transpose) into sources, with the same weights."""

    fwd: RowPlan
    bwd: RowPlan

    @property
    def T(self) -> "PaddedRows":
        return PaddedRows(self.bwd, self.fwd)

    def aggregate(self, h):
        return _rows_spmm(h, self)

    def concat_aggregate(self, h):
        return _rows_concat(h, self)

    def add_aggregate(self, x, h):
        return _fold(h, self.fwd, init=x)


# ------------------------------------------------------------- host build
def slots_per_row(in_degree: np.ndarray) -> int:
    """``K``: the largest power of two at or below the median in-degree
    (8 on both benchmark graphs, where it aggregated up to 16% faster
    than 16 on a TPU v5e and never slower: fewer padding slots outweigh
    more levels)."""
    med = float(np.median(in_degree)) if in_degree.size else 1.0
    return 1 << max(0, int(np.floor(np.log2(max(med, 1.0)))))


def _bucket(n: int, share: float, total: int) -> int:
    """``n`` rounded up to a multiple of ``share · total`` (and of 8)."""
    q = max(8, -(-int(share * total) // 8) * 8)
    return -(-n // q) * q


def _steps(n: int, k: int) -> tuple[int, int]:
    """``(rows B, steps)`` of a level of ``n`` rows, B a multiple of 8: a
    level of more than :data:`STEP_SLOTS` slots is cut into equal steps."""
    cap = max(8, STEP_SLOTS // k // 8 * 8)
    c = -(-n // cap)
    return -(-n // (8 * c)) * 8, c


def _stable_order(keys: np.ndarray) -> np.ndarray:
    """``np.argsort(keys, kind="stable")`` for non-negative integer keys,
    in passes over 16-bit digits, lowest first: numpy sorts 16-bit keys
    by radix, three times faster than a stable sort of the whole key at
    2.5M edges."""
    order = np.arange(keys.size)
    shift = 0
    while True:
        digit = ((keys[order] >> shift) & 0xFFFF).astype(np.uint16)
        order = order[np.argsort(digit, kind="stable")]
        shift += 16
        if not (keys >> shift).any():
            return order


def _plans(src, dst, weights, n_nodes: int, k: int):
    """One :class:`RowPlan` per weight vector, sharing the slot tables
    of the layout that sums ``src`` rows into ``dst`` rows, and the
    layout's slot count."""
    order = _stable_order(dst)
    deg = np.bincount(dst, minlength=n_nodes).astype(np.int64)
    first = np.concatenate([[0], np.cumsum(deg)])
    rank = np.argsort(-deg, kind="stable")
    rank_of = np.empty(n_nodes, np.int32)
    rank_of[rank] = np.arange(n_nodes, dtype=np.int32)
    # level j holds the first n_j nodes of the ranking; a level of fewer
    # than TAIL_SHARE of the nodes and all after it go to the tail
    n_level = [n_nodes]
    while 0 < (n := int((deg > len(n_level) * k).sum())) \
            and n >= TAIL_SHARE * n_nodes:
        n_level.append(n)
    steps = [_steps(n if j == 0 else _bucket(n, ROW_BUCKET, n_nodes), k)
             for j, n in enumerate(n_level)]
    n_rows = max([n_nodes] + [rows * c for rows, c in steps])
    eid_of = lambda pos: order[pos] if order.size else np.zeros_like(pos)
    col = lambda a, real: np.where(real, a, 0) if a.size else \
        np.zeros(real.shape, a.dtype)

    levels = [[] for _ in weights]
    for j, (rows, c) in enumerate(steps):
        r = np.arange(rows * c)
        node = rank[np.minimum(r, n_nodes - 1)] if n_nodes else r
        slot = j * k + np.arange(k)
        real = (r < n_level[j])[:, None] & (slot[None] < deg[node][:, None])
        eid = eid_of(np.where(real, first[node][:, None] + slot[None], 0))
        # (c·B, K) -> (c, K, B): a step's slot k is one contiguous row block
        t = lambda a: jnp.asarray(a.reshape(c, rows, k).transpose(0, 2, 1))
        start = jnp.asarray(np.arange(c, dtype=np.int32) * rows)
        idx = t(col(src[eid] if src.size else src, real).astype(np.int32))
        scale = t(real.astype(np.float32))
        for i, w in enumerate(weights):
            levels[i].append(Level(start, idx, t(col(
                w[eid] if w.size else w, real).astype(np.float32)), scale))

    # the tail: each node's edges past the levels, rows ascending, then
    # padding to its bucket on a row past the accumulator (dropped)
    done = len(n_level) * k
    hubs = rank[:int((deg > done).sum())]
    count = deg[hubs] - done
    n_tail = int(count.sum())
    pad = _bucket(n_tail, TAIL_BUCKET, src.size) - n_tail
    tail_row = np.concatenate([np.repeat(np.arange(hubs.size), count),
                               np.full(pad, n_rows)]).astype(np.int32)
    within = np.arange(n_tail) - np.repeat(np.cumsum(count) - count, count)
    eid = eid_of(np.repeat(first[hubs] + done, count) + within)
    slots = k * sum(n_level[1:]) + k * int((deg > 0).sum()) + n_tail
    tail = lambda a, dtype: jnp.asarray(np.concatenate(
        [a[eid] if a.size else np.zeros(0), np.zeros(pad)]).astype(dtype))
    rank_of = jnp.asarray(rank_of)
    row_node = jnp.asarray(np.concatenate(
        [rank, np.zeros(n_rows - n_nodes, np.int64)]).astype(np.int32))
    tail_row, tail_src = jnp.asarray(tail_row), tail(src, np.int32)
    return ([RowPlan(rank_of, row_node, tuple(lv), tail_row, tail_src,
                     tail(w, np.float32), n_rows)
             for lv, w in zip(levels, weights)], slots)


def padded_rows(edge_src, edge_dst, weights, n_nodes: int):
    """The padded-row layout of a static graph, both directions, for
    each weight vector in ``weights``: a list of :class:`PaddedRows`.

    Built on the host with stable sorts, so the slot order, and with it
    every sum, repeats from build to build.  Counts
    ``graph/agg_layout_builds`` and sets ``graph/agg_slot_share`` (the
    forward layout's slots over edges) in the obs registry."""
    src = np.asarray(edge_src).astype(np.int64)
    dst = np.asarray(edge_dst).astype(np.int64)
    ws = [np.asarray(w, np.float32) for w in weights]
    k = slots_per_row(np.bincount(dst, minlength=n_nodes))
    fwd, slots = _plans(src, dst, ws, n_nodes, k)
    bwd, _ = _plans(dst, src, ws, n_nodes, k)
    reg = get_metrics()
    reg.counter("graph/agg_layout_builds").inc()
    reg.gauge("graph/agg_slot_share").set(slots / max(1, src.size))
    return [PaddedRows(f, b) for f, b in zip(fwd, bwd)]
