"""The plain reference: full-graph GraphSAGE training written straight
from its equations in ``jax.numpy``, importing nothing of the program.

Layer ``i``: ``x = [h, mean_{u -> v} h_u]``, ``z = x @ W + b``, ReLU on
every layer but the last.  Loss: mean softmax cross-entropy over the
training nodes.  Update: AdamW (Loshchilov & Hutter) with bias
correction.  Gradients by autodiff of the forward.

Matmuls run at the precision the configuration states
(``matmul_precision``: ``default`` on a TPU rounds float32 operands to
bfloat16 and accumulates in float32).  ``dtype="bfloat16"`` is the
control: the same computation with every array in bfloat16.

A graph whose edge messages (``E x widest layer x 4`` bytes) pass
:data:`WHOLE_BYTES` sums them block by block of at most
:data:`BLOCK_BYTES` in a ``lax.scan``: the messages are linear in the
layer's input, so the backward keeps no message either and recomputes
each block's as it goes.  Below it the forward is one segment-sum, as
arxiv's and Flickr's cells have always run it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


#: Edge-message bytes above which the forward sums them in blocks.
WHOLE_BYTES = 4e9
#: Message bytes of one block, where the forward is blocked.
BLOCK_BYTES = 1e9


def aggregate(h, src, dst, w):
    """``sum_{u -> v} w_uv h_u`` for every node ``v``: ``src``, ``dst``
    and ``w`` are the edge list, or ``(blocks, B)`` blocks of it padded
    with weight-0 edges from and to node 0."""
    n = h.shape[0]
    if src.ndim == 1:
        return jax.ops.segment_sum(h[src] * w[:, None], dst, num_segments=n)

    def add_block(acc, block):
        s, d, wb = block
        return acc + jax.ops.segment_sum(h[s] * wb[:, None], d,
                                         num_segments=n), None

    return jax.lax.scan(add_block, jnp.zeros_like(h), (src, dst, w))[0]


def blocks_for(n_edges: int, widest: int) -> int | None:
    """Edges per block for a graph of ``n_edges`` whose widest aggregated
    layer is ``widest``; ``None`` where the messages fit whole."""
    if n_edges * widest * 4 <= WHOLE_BYTES:
        return None
    return max(1, int(BLOCK_BYTES // (widest * 4)))


def to_blocks(graph: dict, per_block: int) -> dict:
    """``graph`` with its edge arrays cut into ``(blocks, per_block)``,
    the last block padded with weight-0 edges from and to node 0."""
    e = graph["src"].shape[0]
    pad = -e % per_block
    cut = lambda a: jnp.pad(a, (0, pad)).reshape(-1, per_block)
    return dict(graph, src=cut(graph["src"]), dst=cut(graph["dst"]),
                mean_w=cut(graph["mean_w"]))


def forward(params, feats, src, dst, mean_w, shifts=None):
    """The logits; with ``shifts`` (one zero array per layer, added to
    its pre-activation), also each layer's input ``[h, agg]``."""
    h, inputs = feats, []
    for li, p in enumerate(params):
        agg = aggregate(h, src, dst, mean_w)
        x = jnp.concatenate([h, agg], axis=1)
        z = x @ p["w"] + p["b"]
        if shifts is not None:
            z = z + shifts[li]
            inputs.append(x)
        h = jnp.maximum(z, 0) if li < len(params) - 1 else z
    return h if shifts is None else (h, inputs)


def loss_fn(params, feats, src, dst, mean_w, labels, train):
    logits = forward(params, feats, src, dst, mean_w)
    return _masked_ce(logits, labels, train)


def _masked_ce(logits, labels, train):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    mask = train.astype(logits.dtype)
    return jnp.sum(nll * mask) / jnp.sum(mask)


@partial(jax.jit, static_argnames=("opt",))
def step(params, m, v, t, graph, opt):
    """One AdamW step: returns (loss, grads, params, m, v) with ``t`` the
    1-based step count."""
    loss, grads = jax.value_and_grad(loss_fn)(
        params, graph["features"], graph["src"], graph["dst"],
        graph["mean_w"], graph["labels"], graph["train"])
    lr, b1, b2, eps, wd = opt
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        p = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p)
        return p, m, v

    out = jax.tree.map(upd, params, grads, m, v)
    unzip = lambda i: jax.tree.map(lambda o: o[i], out,
                                   is_leaf=lambda o: isinstance(o, tuple))
    return loss, grads, unzip(0), unzip(1), unzip(2)


@partial(jax.jit, static_argnames=("chips",))
def _chip_grad_norms(params, graph, chip, chips):
    n = graph["features"].shape[0]
    shifts = [jnp.zeros((n, p["b"].shape[0]), p["b"].dtype)
              for p in params]

    def loss(shifts):
        logits, inputs = forward(params, graph["features"], graph["src"],
                                 graph["dst"], graph["mean_w"], shifts)
        return _masked_ce(logits, graph["labels"], graph["train"]), inputs

    dz, inputs = jax.grad(loss, has_aux=True)(shifts)
    return [jnp.stack([jnp.linalg.norm(
        jnp.where((chip == i)[:, None], x, 0).T @ g) for i in range(chips)])
        for x, g in zip(inputs, dz)]


def chip_grad_norms(params, graph: dict, chip, chips: int,
                    precision: str = "default") -> list[list[float]]:
    """The norm of each chip's share of the first weight gradient, layer
    by layer: ``|G_i| = |sum_{v on chip i} x_v^T dz_v|`` over the rows
    ``chip`` (an ``(N,)`` array) puts on chip ``i``, in float32 at
    ``precision``."""
    graph = _blocked({k: graph[k] for k in ("features", "src", "dst",
                                            "mean_w", "labels", "train")},
                     params)
    with jax.default_matmul_precision(precision):
        norms = _chip_grad_norms(params, graph,
                                 jnp.asarray(chip, jnp.int32), chips)
    return [[float(v) for v in layer] for layer in norms]


def _blocked(graph: dict, params) -> dict:
    """``graph`` cut into edge blocks where its messages do not fit
    whole (:func:`blocks_for`)."""
    widest = max(p["w"].shape[0] // 2 for p in params)
    per_block = blocks_for(graph["src"].shape[0], widest)
    return graph if per_block is None else to_blocks(graph, per_block)


def train3(params, graph: dict, optimizer: dict, dtype: str = "float32",
           precision: str = "default"):
    """Three reference steps from ``params`` on ``graph``, with every
    array in ``dtype`` and matmuls at ``precision``: returns the three
    losses, the first gradient and the parameters after the third update
    (host numpy)."""
    import numpy as np

    dt = jnp.dtype(dtype)
    cast = lambda x: x.astype(dt) if jnp.issubdtype(x.dtype,
                                                    jnp.floating) else x
    params = jax.tree.map(cast, params)
    graph = _blocked({k: cast(graph[k]) for k in ("features", "src", "dst",
                                                  "mean_w", "labels",
                                                  "train")}, params)
    opt = tuple(float(optimizer[k])
                for k in ("lr", "b1", "b2", "eps", "weight_decay"))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        for t in (1, 2, 3):
            loss, grads, params, m, v = step(params, m, v,
                                             jnp.asarray(t, dt), graph, opt)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = jax.tree.map(
                    lambda g: np.asarray(g, np.float32), grads)
    return (losses, first_grad,
            jax.tree.map(lambda p: np.asarray(p, np.float32), params))
