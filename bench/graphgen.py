"""Synthetic graphs at a configuration's published counts, made on the
device from the seed in one jitted call.

The same generative family as the program's ``arxiv_like`` /
``flickr_like`` stand-ins (uniform sources, skewed in-degree
``floor(N * u**2)``, a homophilous rewiring of a share of the
destinations to a node of the source's class, class-centred Gaussian
features, a 60/20/20 split), vectorised with no per-edge loop and with
no sort, which the TPU compiler takes most of a minute to build at these
sizes.  Classes take turns along the node index from a random offset, so
a node of a given class is drawn in closed form; the split ranks nodes by
a random affine bijection of the index.  Every seed yields the same
sizes: exactly ``n_edges`` undirected edges with no self pair (a drawn
self pair is moved to the next node), symmetrised, plus one self loop
per node, so ``2 * n_edges + n_nodes`` directed edges, and split counts
of ``int(share * n_nodes)``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

#: Multipliers of the split's affine bijection ``(a * v + b) mod N``:
#: primes small enough that ``a * v`` stays inside int32 for the node
#: counts configured here; one that divides N is skipped.
_SPLIT_PRIMES = (7919, 7927, 7933, 7937, 7949, 7951, 7963, 7993)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, including ones wider than 32
    bits: the low 31 bits seed the key, the rest are folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFF_FFFF),
                              seed >> 31)


@partial(jax.jit, static_argnames=("n_nodes", "n_edges", "n_feats",
                                   "n_classes", "homophily",
                                   "feature_noise", "train_share",
                                   "val_share"))
def make_graph(key, *, n_nodes: int, n_edges: int, n_feats: int,
               n_classes: int, homophily: float, feature_noise: float,
               train_share: float, val_share: float) -> dict:
    """Arrays of one graph: ``features`` (N, F) f32, ``labels`` (N,) i32,
    ``src``/``dst`` (2E + N,) i32, ``mean_w``/``gcn_w`` (2E + N,) f32 and
    the ``train``/``val``/``test`` masks (N,) bool."""
    n, e, c = n_nodes, n_edges, n_classes
    if n * max(_SPLIT_PRIMES) >= 2 ** 31:
        raise ValueError(f"n_nodes={n} is too large for the split")
    k = jax.random.split(key, 8)
    offset = jax.random.randint(k[0], (), 0, c, jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    labels = (idx + offset) % c
    src = jax.random.randint(k[1], (e,), 0, n, jnp.int32)
    u = jax.random.uniform(k[2], (e,))
    dst = jnp.minimum((n * u * u).astype(jnp.int32), n - 1)
    # homophily: a share of the destinations becomes a uniform node of
    # the source's class: class l holds the nodes first(l) + c * j
    first = (labels[src] - offset) % c
    count = (n - first + c - 1) // c
    j = jnp.minimum((jax.random.uniform(k[3], (e,)) * count)
                    .astype(jnp.int32), count - 1)
    rewire = jax.random.uniform(k[4], (e,)) < homophily
    dst = jnp.where(rewire, first + c * j, dst)
    dst = jnp.where(dst == src, (dst + 1) % n, dst)

    loops = idx
    s_all = jnp.concatenate([src, dst, loops])
    d_all = jnp.concatenate([dst, src, loops])
    deg = jax.ops.segment_sum(jnp.ones_like(d_all, jnp.float32), d_all,
                              num_segments=n)
    mean_w = 1.0 / deg[d_all]
    gcn_w = jax.lax.rsqrt(deg[s_all] * deg[d_all])

    centers = jax.random.normal(k[5], (c, n_feats), jnp.float32)
    feats = centers[labels] + feature_noise * jax.random.normal(
        k[6], (n, n_feats), jnp.float32)

    primes = jnp.asarray([p for p in _SPLIT_PRIMES if math.gcd(p, n) == 1],
                         jnp.int32)
    a = primes[jax.random.randint(k[7], (), 0, primes.shape[0])]
    b = jax.random.randint(jax.random.fold_in(k[7], 1), (), 0, n, jnp.int32)
    rank = (a * idx + b) % n
    n_tr, n_va = int(train_share * n), int(val_share * n)
    return {"features": feats, "labels": labels, "src": s_all,
            "dst": d_all, "mean_w": mean_w, "gcn_w": gcn_w,
            "train": rank < n_tr,
            "val": (rank >= n_tr) & (rank < n_tr + n_va),
            "test": rank >= n_tr + n_va}


def graph_from_config(config: dict, seed: int) -> dict:
    """:func:`make_graph` at a configuration file's counts."""
    gr, gen = config["graph"], config["generator"]
    return make_graph(seed_key(seed), n_nodes=gr["n_nodes"],
                      n_edges=gr["n_edges"], n_feats=gr["n_feats"],
                      n_classes=gr["n_classes"],
                      homophily=gen["homophily"],
                      feature_noise=gen["feature_noise"],
                      train_share=gen["split"][0],
                      val_share=gen["split"][1])


@partial(jax.jit, static_argnames=("dims",))
def init_params(key, dims: tuple[int, ...]) -> list[dict]:
    """GraphSAGE weights: layer ``i`` maps the concatenation of a node's
    row and its neighbours' mean (2 * dims[i] wide) to dims[i + 1];
    ``w ~ N(0, 1 / fan_in)``, ``b = 0``.  One jitted call on the device."""
    params = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        fan_in = 2 * d_in
        params.append({"w": jax.random.normal(sub, (fan_in, d_out),
                                              jnp.float32) / fan_in ** 0.5,
                       "b": jnp.zeros((d_out,), jnp.float32)})
    return params


def layer_dims(config: dict) -> tuple[int, ...]:
    gr = config["graph"]
    return (gr["n_feats"], *config["model"]["hidden"], gr["n_classes"])


def weights_from_config(config: dict, seed: int) -> list[dict]:
    """The cell's initial weights from the seed (a stream apart from the
    graph's)."""
    return init_params(jax.random.fold_in(seed_key(seed), 1),
                       layer_dims(config))
