"""GCN (Kipf-Welling, paper Eq. 1) and GraphSAGE with EXACT-style
activation compression.

Compression placement matches EXACT/i-EXACT exactly:

* the dense input of every linear is stored compressed
  (:func:`repro.core.compressed_matmul`) — RP + block-wise SR quant;
* ReLU saves a packed 1-bit sign mask (:func:`relu_1bit`), never the tensor;
* the sparse aggregation ``Â·`` is linear in H — its VJP needs only the
  graph's structure and weights (:mod:`repro.graph.adjacency`), so it
  stores no float activations at all.

The compress/decompress execution strategy is picked by
``CompressionConfig.impl`` (see :mod:`repro.core.backend`);
:meth:`GNNConfig.with_impl` flips a whole training job between the
reference and fused kernel backends with bit-identical codes.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pack as packmod
from repro.core.act_compress import compressed_matmul, zero_ct  # noqa: F401
from repro.core.compressor import CompressionConfig
from repro.engine.seeds import layer_seed
from repro.obs import scopes


# ------------------------------------------------------------- 1-bit ReLU
@jax.custom_vjp
def relu_1bit(z):
    return jnp.maximum(z, 0.0)


def _relu_fwd(z):
    # Pack the sign bits of the *flattened* tensor as one row: rank-agnostic
    # (scalars, vectors, (N, F) maps, stacked/batched rank>=3 inputs) and at
    # most 31 wasted bits total, vs one word per row of a 2-D reshape.
    with jax.named_scope(scopes.RELU_MASK):
        mask = packmod.pack((z > 0).astype(jnp.int32).reshape(1, -1), 1)
    return jnp.maximum(z, 0.0), (mask, z.shape)


def _relu_bwd(res, g):
    mask, shape = res
    with jax.named_scope(scopes.RELU_MASK):
        m = packmod.unpack(mask, 1, int(np.prod(shape, dtype=np.int64)))
        return (g * m.reshape(shape).astype(g.dtype),)


relu_1bit.defvjp(_relu_fwd, _relu_bwd)


# ------------------------------------------------------------------ SpMM
def spmm(h, adj):
    """``out[d] += w_e * h[s]`` over the edges of ``adj``, an aggregation
    operand of :mod:`repro.graph.adjacency`: a static graph's padded-row
    layout (gather and add, its transpose as the VJP) or a traced edge
    list (segment-sum).  ``adj.T`` is the transposed product."""
    return adj.aggregate(h)


# ----------------------------------------------------------------- model
@dataclasses.dataclass(frozen=True)
class GNNConfig:
    """``compression`` is heterogeneous-precision aware: a single
    ``CompressionConfig`` is broadcast to every layer (the original
    homogeneous behavior), while a tuple carries one entry per GNN layer
    (``len(hidden) + 1``; ``None`` entries leave that layer uncompressed).
    :meth:`layer_compression` is the normalized per-layer view every
    consumer (forward pass, memory model, allocator) reads."""

    arch: str = "sage"                 # "gcn" | "sage"
    hidden: tuple[int, ...] = (256, 256)
    n_classes: int = 40
    compression: (CompressionConfig | None
                  | tuple[CompressionConfig | None, ...]) = None
    dropout: float = 0.0

    @property
    def n_layers(self) -> int:
        return len(self.hidden) + 1

    def layer_compression(self) -> tuple[CompressionConfig | None, ...]:
        """Per-layer compression configs, broadcasting a shared one."""
        if self.compression is None:
            return (None,) * self.n_layers
        if isinstance(self.compression, CompressionConfig):
            return (self.compression,) * self.n_layers
        per = tuple(self.compression)
        if len(per) != self.n_layers:
            raise ValueError(
                f"per-layer compression tuple has {len(per)} entries for a "
                f"{self.n_layers}-layer model")
        return per

    def with_layer_bits(self, bits) -> "GNNConfig":
        """Pin each layer's quantization width (autoprec's output).

        ``bits`` holds one entry per layer; entries that are falsy (0/None)
        or land on an uncompressed layer leave that layer untouched.
        """
        per = self.layer_compression()
        if len(bits) != self.n_layers:
            raise ValueError(
                f"got {len(bits)} bit-widths for {self.n_layers} layers")
        new = tuple(
            c if c is None or not b else dataclasses.replace(c, bits=int(b))
            for c, b in zip(per, bits))
        return dataclasses.replace(self, compression=new)

    def with_impl(self, impl: str) -> "GNNConfig":
        """Same model, compression routed through a different kernel backend.

        No-op on an uncompressed config — fp32 baselines stay valid inside
        backend sweeps (there is no compression stack to reroute).
        """
        if self.compression is None:
            return self
        if isinstance(self.compression, CompressionConfig):
            return dataclasses.replace(
                self, compression=self.compression.with_impl(impl))
        return dataclasses.replace(self, compression=tuple(
            None if c is None else c.with_impl(impl)
            for c in self.compression))


def _dims(cfg: GNNConfig, in_dim: int):
    return [in_dim, *cfg.hidden, cfg.n_classes]


def init_gnn_params(key, cfg: GNNConfig, in_dim: int):
    dims = _dims(cfg, in_dim)
    params = []
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        key, sub = jax.random.split(key)
        fan_in = d_in * (2 if cfg.arch == "sage" else 1)
        w = jax.random.normal(sub, (fan_in, d_out), jnp.float32) / np.sqrt(fan_in)
        params.append({"w": w, "b": jnp.zeros((d_out,), jnp.float32)})
    return params


def _maybe_compressed_matmul(x, w, comp: CompressionConfig | None, seed):
    if comp is None:
        return x @ w
    return compressed_matmul(x, w, seed, comp)


def gnn_forward(params, graph, cfg: GNNConfig, seed=0, dropout_key=None,
                node_mask=None, plan=None, offload=None):
    """graph = (features, gcn_adj, mean_adj): the features and the
    aggregation operands under the GCN and the mean weights
    (:func:`graph_tuple`, ``SubgraphBatch.graph_tuple``).

    ``node_mask`` ((N,) f32, optional) marks valid rows of a padded subgraph
    batch: activations of masked-out rows are pinned to zero after every
    layer, so the compressed stashes (``compressed_matmul`` inputs, ReLU
    sign masks) see clean zeros on padding instead of bias leakage, and
    quantization block statistics stay unpolluted.  ``None`` (full graph)
    is the existing behavior, bit for bit.

    ``plan`` (a :class:`repro.offload.arena.StashPlan`, optional) reroutes
    every layer's saved-for-backward stash through the pooled arena under
    the ``offload`` policy ("device" | "host" | "pinned-paged" — see
    :mod:`repro.offload.engine`); forward values and stash bits are
    identical to the per-tensor path.
    """
    if plan is not None:
        if dropout_key is not None and cfg.dropout:
            raise ValueError("arena-routed forward does not support dropout")
        from repro.engine.forward import arena_gnn_forward

        return arena_gnn_forward(params, graph, cfg, plan, seed=seed,
                                 node_mask=node_mask,
                                 policy=offload or "device")
    feats, gcn_adj, mean_adj = graph
    h = feats if node_mask is None else feats * node_mask[:, None]
    seed = jnp.asarray(seed, jnp.uint32)
    per_layer = cfg.layer_compression()
    for li, p in enumerate(params):
        lseed = layer_seed(seed, li)
        comp = per_layer[li]
        if cfg.arch == "gcn":
            z = _maybe_compressed_matmul(h, p["w"], comp, lseed) + p["b"]
            z = spmm(z, gcn_adj)
        else:  # sage
            x = mean_adj.concat_aggregate(h)   # [h, Â·h]
            z = _maybe_compressed_matmul(x, p["w"], comp, lseed) + p["b"]
        if li < len(params) - 1:
            z = relu_1bit(z)
            if cfg.dropout and dropout_key is not None:
                dropout_key, sub = jax.random.split(dropout_key)
                keep = jax.random.bernoulli(sub, 1 - cfg.dropout, z.shape)
                z = jnp.where(keep, z / (1 - cfg.dropout), 0.0)
        h = z if node_mask is None else z * node_mask[:, None]
    return h


def graph_tuple(g):
    """The jit-stable ``(features, gcn_adj, mean_adj)`` of a full Graph:
    its padded-row layout, built once per graph."""
    return (g.features, *g.aggregation)
