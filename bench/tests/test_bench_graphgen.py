"""The generator: the configured graphs are the bits they always were,
a graph of any node count up to 2**30 gets its split, and a fixed
structure seed fixes the edges and the labels alone."""
import hashlib
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import graphgen  # noqa: E402

#: sha256 (first 16 hex digits) over every array of the graph, by key,
#: as the generator made them before it took graphs above 268,668 nodes.
DIGESTS = {
    ("sage-arxiv", 7): "a8564c41a3f5d1bc",
    ("sage-arxiv", 2**31 + 11): "1b7703527b264afd",
    ("sage-arxiv", 3000000301): "696d2e97469f266c",
    ("sage-flickr", 7): "b11e31128f13f691",
    ("sage-flickr", 2**31 + 11): "b2a48bcd56784bbf",
    ("sage-flickr", 3000000301): "200a9b07e08069c1",
}


def _digest(graph: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(graph):
        h.update(k.encode())
        h.update(np.asarray(graph[k]).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("config,seed", sorted(DIGESTS),
                         ids=[f"{c}-{s}" for c, s in sorted(DIGESTS)])
def test_configured_graphs_are_unchanged(config, seed):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    assert _digest(graphgen.graph_from_config(cfg, seed)) == \
        DIGESTS[(config, seed)]


def test_mulmod_matches_integers():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    for n in (268_669, 867_656, 2**30 - 1):
        v = np.concatenate([rng.integers(0, n, 1000), [0, n - 1]])
        for a in graphgen._SPLIT_PRIMES:
            got = np.asarray(graphgen._mulmod(jnp.int32(a),
                                              jnp.asarray(v, jnp.int32), n))
            assert (got == (a * v.astype(object)) % n).all()


def _graph(n, seed=5, **kw):
    return graphgen.make_graph(graphgen.seed_key(seed), n_nodes=n,
                               n_edges=2000, n_feats=4, n_classes=3,
                               homophily=0.5, feature_noise=1.0,
                               train_share=0.6, val_share=0.2, **kw)


@pytest.mark.parametrize("n", [graphgen._WIDE_NODES - 1,
                               graphgen._WIDE_NODES, 867_656])
def test_split_is_a_bijection_at_any_size(n):
    g = _graph(n)
    tr, va, te = (np.asarray(g[k]) for k in ("train", "val", "test"))
    assert tr.sum() == int(0.6 * n) and va.sum() == int(0.2 * n)
    assert (tr.astype(int) + va + te == 1).all()


def test_structure_seed_fixes_edges_and_labels_only():
    key = graphgen.seed_key(0)
    a, b = _graph(5000, 11, structure_key=key), _graph(5000, 12,
                                                       structure_key=key)
    for k in ("src", "dst", "labels", "mean_w", "gcn_w"):
        assert (np.asarray(a[k]) == np.asarray(b[k])).all(), k
    for k in ("features", "train"):
        assert (np.asarray(a[k]) != np.asarray(b[k])).any(), k
