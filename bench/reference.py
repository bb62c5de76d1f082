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
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def forward(params, feats, src, dst, mean_w):
    n = feats.shape[0]
    h = feats
    for li, p in enumerate(params):
        agg = jax.ops.segment_sum(h[src] * mean_w[:, None], dst,
                                  num_segments=n)
        z = jnp.concatenate([h, agg], axis=1) @ p["w"] + p["b"]
        h = jnp.maximum(z, 0) if li < len(params) - 1 else z
    return h


def loss_fn(params, feats, src, dst, mean_w, labels, train):
    logits = forward(params, feats, src, dst, mean_w)
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
    graph = {k: cast(graph[k]) for k in ("features", "src", "dst",
                                         "mean_w", "labels", "train")}
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
