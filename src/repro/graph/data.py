"""Synthetic graph datasets with OGB-Arxiv / Flickr matched statistics.

The benchmark datasets are not downloadable in this offline container
(DESIGN.md §2), so we generate stochastic-block-model-flavoured stand-ins:
power-law-ish degrees, homophilous edges, class-conditional Gaussian
features — enough learnable structure that a GCN/SAGE materially beats the
class prior, which is what the paper's accuracy-parity claims need.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax.numpy as jnp

from repro.graph.adjacency import PaddedRows, padded_rows


@dataclasses.dataclass
class Graph:
    name: str
    features: jnp.ndarray        # (N, F) f32
    labels: jnp.ndarray          # (N,) i32
    edge_src: jnp.ndarray        # (E,) i32  — includes self loops, directed both ways
    edge_dst: jnp.ndarray        # (E,) i32
    gcn_weight: jnp.ndarray      # (E,) f32  — D̃^{-1/2}(A+I)D̃^{-1/2} entries
    mean_weight: jnp.ndarray     # (E,) f32  — row-mean aggregation weights
    train_mask: jnp.ndarray      # (N,) bool
    val_mask: jnp.ndarray
    test_mask: jnp.ndarray
    num_classes: int

    @property
    def n_nodes(self) -> int:
        return int(self.features.shape[0])

    @property
    def n_feats(self) -> int:
        return int(self.features.shape[1])

    @property
    def n_edges(self) -> int:
        return int(self.edge_src.shape[0])

    @functools.cached_property
    def aggregation(self) -> tuple[PaddedRows, PaddedRows]:
        """``(gcn, mean)``: the aggregation operands of the full graph,
        its padded-row layout under the GCN and the mean weights, built
        on first use and kept with the graph."""
        gcn, mean = padded_rows(self.edge_src, self.edge_dst,
                                (self.gcn_weight, self.mean_weight),
                                self.n_nodes)
        return gcn, mean


def in_adjacency(edge_src, edge_dst, n_nodes: int):
    """CSR over *destination*: ``(nbr, starts)`` with the in-neighbors
    (message sources) of node ``u`` at ``nbr[starts[u]:starts[u+1]]``.

    numpy-side helper for partitioners/samplers.  A full graph aggregates
    through its padded-row layout (:attr:`Graph.aggregation`), built from
    the same stable destination sort; partition batches and the mesh
    engine's tables aggregate their edge lists directly."""
    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    order = np.argsort(dst, kind="stable")
    starts = np.searchsorted(dst[order], np.arange(n_nodes + 1))
    return src[order], starts


def synthetic_graph(name: str, n_nodes: int, n_edges: int, n_feats: int,
                    n_classes: int, homophily: float = 0.65,
                    feature_noise: float = 1.0, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, n_nodes)

    # power-law-ish degree skew: dst index drawn as floor(N * u^2)
    src = rng.integers(0, n_nodes, n_edges)
    dst = (n_nodes * rng.random(n_edges) ** 2).astype(np.int64)
    # homophily: rewire a fraction of edges to a same-class destination
    same = rng.random(n_edges) < homophily
    by_class = [np.flatnonzero(labels == c) for c in range(n_classes)]
    rewired = np.array(
        [by_class[labels[s]][rng.integers(len(by_class[labels[s]]))]
         if m else d for s, d, m in zip(src, dst, same)], dtype=np.int64)
    dst = rewired
    keep = src != dst
    src, dst = src[keep], dst[keep]

    # symmetrize + self loops
    s_all = np.concatenate([src, dst, np.arange(n_nodes)])
    d_all = np.concatenate([dst, src, np.arange(n_nodes)])

    deg = np.bincount(d_all, minlength=n_nodes).astype(np.float64)
    gcn_w = 1.0 / np.sqrt(deg[s_all] * deg[d_all])
    mean_w = 1.0 / deg[d_all]

    centers = rng.normal(0, 1, (n_classes, n_feats))
    feats = centers[labels] + feature_noise * rng.normal(0, 1, (n_nodes, n_feats))

    perm = rng.permutation(n_nodes)
    n_tr, n_va = int(0.6 * n_nodes), int(0.2 * n_nodes)
    train_mask = np.zeros(n_nodes, bool)
    val_mask = np.zeros(n_nodes, bool)
    test_mask = np.zeros(n_nodes, bool)
    train_mask[perm[:n_tr]] = True
    val_mask[perm[n_tr:n_tr + n_va]] = True
    test_mask[perm[n_tr + n_va:]] = True

    return Graph(
        name=name,
        features=jnp.asarray(feats, jnp.float32),
        labels=jnp.asarray(labels, jnp.int32),
        edge_src=jnp.asarray(s_all, jnp.int32),
        edge_dst=jnp.asarray(d_all, jnp.int32),
        gcn_weight=jnp.asarray(gcn_w, jnp.float32),
        mean_weight=jnp.asarray(mean_w, jnp.float32),
        train_mask=jnp.asarray(train_mask),
        val_mask=jnp.asarray(val_mask),
        test_mask=jnp.asarray(test_mask),
        num_classes=n_classes,
    )


def stream_edge_chunks(n_nodes: int, n_edges: int, *, labels=None,
                       homophily: float = 0.0, seed: int = 0,
                       chunk_edges: int = 1 << 18):
    """Yield the synthetic edge stream as ``(src, dst)`` chunks with
    O(chunk) host memory.

    The same generative family as :func:`synthetic_graph` — uniform
    sources, power-law-ish destinations (``floor(N·u²)``), an optional
    homophilous rewiring of a ``homophily`` fraction of destinations to a
    same-class node — but fully vectorized per chunk and never
    materializing the edge list: the papers100M-scale generator's
    building block.  Self loops are filtered per chunk (so chunk lengths
    vary slightly; the *drawn* count is exact).

    Homophilous rewiring picks, for each rewired edge, a uniform node of
    the source's class via one ``argsort(labels)`` table shared across
    chunks — vectorized, unlike ``synthetic_graph``'s per-edge Python
    loop (kept untouched upstream: its draw order defines the existing
    datasets' bits).
    """
    rng = np.random.default_rng(seed)
    order = starts = None
    if homophily > 0.0:
        if labels is None:
            raise ValueError("homophily > 0 needs labels")
        labels = np.asarray(labels)
        order = np.argsort(labels, kind="stable")
        n_classes = int(labels.max()) + 1
        starts = np.searchsorted(labels[order], np.arange(n_classes + 1))
    done = 0
    while done < n_edges:
        k = min(chunk_edges, n_edges - done)
        src = rng.integers(0, n_nodes, k)
        dst = (n_nodes * rng.random(k) ** 2).astype(np.int64)
        if homophily > 0.0:
            rew = rng.random(k) < homophily
            ls = labels[src[rew]]
            lo, hi = starts[ls], starts[ls + 1]
            dst[rew] = order[lo + rng.integers(0, hi - lo)]
        keep = src != dst
        yield src[keep], dst[keep]
        done += k


def synthetic_graph_streamed(name: str, n_nodes: int, n_edges: int,
                             n_feats: int, n_classes: int,
                             homophily: float = 0.0,
                             feature_noise: float = 1.0, seed: int = 0,
                             chunk_edges: int = 1 << 18) -> Graph:
    """:func:`synthetic_graph`'s Graph assembled from
    :func:`stream_edge_chunks` — same symmetrize/self-loop/normalization
    pipeline, but degrees accumulate per chunk (one ``bincount`` pass)
    and the host never holds more than one chunk of intermediate draw
    state.  Used for the papers100M-scale mesh benchmarks; the classic
    datasets keep :func:`synthetic_graph` (different draw order, so
    different — frozen — bits).
    """
    rng = np.random.default_rng(seed + 1)
    labels = rng.integers(0, n_classes, n_nodes)
    deg = np.zeros(n_nodes, np.int64)
    srcs, dsts = [np.arange(n_nodes)], [np.arange(n_nodes)]
    deg += 1  # self loops
    for src, dst in stream_edge_chunks(n_nodes, n_edges, labels=labels,
                                       homophily=homophily, seed=seed,
                                       chunk_edges=chunk_edges):
        # symmetrize chunk-locally: both directions land in the stream
        srcs.extend([src, dst])
        dsts.extend([dst, src])
        deg += np.bincount(dst, minlength=n_nodes)
        deg += np.bincount(src, minlength=n_nodes)
    s_all = np.concatenate(srcs)
    d_all = np.concatenate(dsts)
    degf = deg.astype(np.float64)
    gcn_w = 1.0 / np.sqrt(degf[s_all] * degf[d_all])
    mean_w = 1.0 / degf[d_all]

    centers = rng.normal(0, 1, (n_classes, n_feats))
    feats = (centers[labels]
             + feature_noise * rng.normal(0, 1, (n_nodes, n_feats)))

    perm = rng.permutation(n_nodes)
    n_tr, n_va = int(0.6 * n_nodes), int(0.2 * n_nodes)
    train_mask = np.zeros(n_nodes, bool)
    val_mask = np.zeros(n_nodes, bool)
    test_mask = np.zeros(n_nodes, bool)
    train_mask[perm[:n_tr]] = True
    val_mask[perm[n_tr:n_tr + n_va]] = True
    test_mask[perm[n_tr + n_va:]] = True

    return Graph(
        name=name,
        features=jnp.asarray(feats, jnp.float32),
        labels=jnp.asarray(labels, jnp.int32),
        edge_src=jnp.asarray(s_all, jnp.int32),
        edge_dst=jnp.asarray(d_all, jnp.int32),
        gcn_weight=jnp.asarray(gcn_w, jnp.float32),
        mean_weight=jnp.asarray(mean_w, jnp.float32),
        train_mask=jnp.asarray(train_mask),
        val_mask=jnp.asarray(val_mask),
        test_mask=jnp.asarray(test_mask),
        num_classes=n_classes,
    )


def papers100m_like(scale: float = 1e-4, seed: int = 0) -> Graph:
    """ogbn-papers100M stand-in: 111,059,956 nodes / 1.6B edges / 128
    feats / 172 classes, streamed down by ``scale``.

    The mesh engine's scale target (ISSUE 7): big enough at small scales
    to exercise partition-parallel sharding + the host-resident feature
    pager, generated via :func:`synthetic_graph_streamed` so host memory
    stays O(chunk) during edge synthesis.
    """
    n = max(4096, int(111_059_956 * scale))
    e = max(8 * n, int(1_615_685_872 * scale))
    return synthetic_graph_streamed("papers100m-like", n, e, 128, 172,
                                    homophily=0.4, feature_noise=2.5,
                                    seed=seed)


def arxiv_like(scale: float = 0.1, seed: int = 0) -> Graph:
    """OGB-Arxiv stand-in: 169,343 nodes / ~1.17M edges / 128 feats / 40 cls.

    Noise/homophily tuned so a 3-layer SAGE lands mid-range (~0.7), leaving
    headroom for compression-induced accuracy loss to show if it existed —
    mirrors the paper's Table 1 operating point (71.95% FP32).
    """
    n = max(512, int(169_343 * scale))
    e = max(4 * n, int(1_166_243 * scale))
    return synthetic_graph("arxiv-like", n, e, 128, 40, homophily=0.5,
                           feature_noise=2.0, seed=seed)


def cora_like(scale: float = 1.0, seed: int = 0) -> Graph:
    """Cora stand-in: 2,708 nodes / ~10.5K edges / 128 feats / 7 classes.

    The classic citation-network smoke config — small enough that offload
    parity gates (host-vs-device loss trajectories) run in seconds on
    CPU, with the real datasets' class count and edge density.  Feature
    dim is 128 (not Cora's 1433 bag-of-words) to keep CPU matmuls cheap.
    """
    n = max(256, int(2_708 * scale))
    e = max(4 * n, int(10_556 * scale))
    return synthetic_graph("cora-like", n, e, 128, 7, homophily=0.6,
                           feature_noise=1.5, seed=seed)


def flickr_like(scale: float = 0.1, seed: int = 0) -> Graph:
    """Flickr stand-in: 89,250 nodes / ~900K edges / 500 feats / 7 classes.

    Tuned toward the paper's ~51.8% FP32 operating point (hard task)."""
    n = max(512, int(89_250 * scale))
    e = max(4 * n, int(899_756 * scale))
    return synthetic_graph("flickr-like", n, e, 500, 7, homophily=0.4,
                           feature_noise=3.0, seed=seed)
