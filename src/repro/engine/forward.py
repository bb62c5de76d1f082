"""THE stash-aware GNN forward: one ``custom_vjp`` over the whole network
for every training path.

Every combination of the engine's stash axis routes through this single
implementation — classic per-tensor residuals (``StashPolicy(kind=
"tensor")``), pooled device arenas, and host-offloaded arenas — by
swapping the writer/reader pair from :mod:`repro.offload.engine`.  Before
the engine refactor this forward existed twice: implicitly, as the
composition of the per-op ``compressed_matmul`` / ``relu_1bit``
``custom_vjp``s autodiff stitched together inside ``graph/train.py``'s
two step builders, and explicitly as the arena-routed whole-net
``custom_vjp`` in ``offload/gnn.py``.  Both spellings produce
bit-identical gradients (the manual walk below *is* what autodiff
emitted), so they collapsed into this one.

Forward: exactly :func:`repro.graph.models.gnn_forward` — same layer
math, same per-layer seeds (:func:`repro.engine.seeds.layer_seed`), same
padding-mask pinning — except every layer's stash (compressed linear
input, or raw f32 for uncompressed layers, plus the packed 1-bit ReLU
sign mask) goes through the policy's writer.

Backward: a manual layer-by-layer reverse walk mirroring what autodiff
produces on the per-op path — ``dx = g @ wᵀ`` exact, ``dw = x̂ᵀ g`` at
the reconstruction (EXACT's estimator), ReLU via the saved sign mask,
and the Â-product transposed through the operand's ``T`` (the other
direction's padded-row layout, or the edge list with src/dst swapped),
the same product ``spmm``'s own VJP takes.
Arena readers prefetch layer ``li-1``'s segments before layer ``li``'s
gradient math so host→device copies run one layer ahead
(double-buffered); the per-tensor reader's prefetch is a no-op.

Cotangents are returned for params and features; the aggregation
operands and the padding mask are non-differentiable graph constants
(zero cotangents) —
the training engines only ever differentiate with respect to params.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pack as packmod
from repro.core.compressor import compress_matmul, decompress_matmul
from repro.engine import seeds
from repro.engine.plan import StashPolicy
from repro.graph.adjacency import EdgeList, zero_cotangent
from repro.obs import scopes
from repro.offload import engine as stash_engine
from repro.offload.arena import StashPlan
from repro.offload.gnn import plan_gnn_stashes  # noqa: F401  (re-export)

#: The per-tensor policy every plain (non-offload) training path uses.
TENSOR_STASH = StashPolicy(kind="tensor", placement="device")


@functools.lru_cache(maxsize=None)
def _build(cfg, plan: StashPlan, stash: StashPolicy, fused: str = "auto"):
    """The custom_vjp forward for one (GNNConfig, StashPlan, StashPolicy,
    fused-mode) tuple.

    ``fused`` is :class:`repro.engine.plan.KernelPolicy`'s knob for the
    quantize-in-epilogue matmul pair; routing (and the per-layer unfused
    fallback) lives in :func:`repro.core.backend.route_fused`, reached
    here through the ``compress_matmul`` / ``decompress_matmul``
    orchestrators."""
    # deferred import: graph.models lazily dispatches into this module;
    # sharing models' spmm keeps the Â-product — and hence the bit-parity
    # contract — single-sourced
    from repro.graph.models import spmm as _spmm

    from repro.graph.models import gnn_forward
    from repro.obs.metrics import get_metrics

    # every _build body is an lru_cache miss — i.e. a fresh custom_vjp
    # trace the plan compiler will pay for; the obs registry counts them
    # as the engine's recompile pressure
    get_metrics().counter("engine/forward_builds").inc()

    per_layer = cfg.layer_compression()
    sage = cfg.arch == "sage"
    L = len(plan.layers)

    def layer_input(h, mean_adj):
        if not sage:
            return h
        with jax.named_scope(scopes.AGG_FWD):
            agg = _spmm(h, mean_adj)
        return jnp.concatenate([h, agg], axis=1)

    @jax.custom_vjp
    def f(params, feats, gcn_adj, mean_adj, seed, nm):
        # primal path (un-differentiated calls): the per-op forward is
        # value-identical and stash-free (compressed_matmul / relu_1bit
        # primals are plain x @ w / maximum), so don't re-state the layer
        # math a third time
        return gnn_forward(params, (feats, gcn_adj, mean_adj), cfg,
                           seed=seed, node_mask=nm)

    def f_fwd(params, feats, gcn_adj, mean_adj, seed, nm):
        writer = stash_engine.make_writer(plan, stash.placement, seed,
                                          kind=stash.kind)
        h = feats * nm[:, None]
        for li, p in enumerate(params):
            lseed = seeds.layer_seed(seed, li)
            x = layer_input(h, mean_adj)
            comp = per_layer[li]
            with jax.named_scope(scopes.DENSE):
                if comp is None:
                    y = x @ p["w"]
                else:
                    # fused path: x is quantized+packed in the matmul
                    # epilogue (one HBM read of x); routing falls back to
                    # the unfused compress + x @ w spelling per layer when
                    # declined
                    y, ct = compress_matmul(x, p["w"], comp, lseed,
                                            fused=fused)
                z = y + p["b"]
            if comp is None:
                writer.put_raw(li, x)
            else:
                writer.put_ct(li, ct)
            if not sage:
                with jax.named_scope(scopes.AGG_FWD):
                    z = _spmm(z, gcn_adj)
            if li < L - 1:
                with jax.named_scope(scopes.RELU_MASK):
                    mask = packmod.pack(
                        (z > 0).astype(jnp.int32).reshape(1, -1), 1)
                writer.put_mask(li, mask)
                with jax.named_scope(scopes.DENSE):
                    z = jnp.maximum(z, 0.0)
            h = z * nm[:, None]
        return h, (params, gcn_adj, mean_adj, nm, writer.residual())

    def f_bwd(res, gy):
        params, gcn_adj, mean_adj, nm, residual = res
        reader = stash_engine.make_reader(plan, stash.placement, residual,
                                          kind=stash.kind)
        reader.prefetch(L - 1)
        gh = gy
        dparams = [None] * L
        for li in reversed(range(L)):
            if li > 0:
                reader.prefetch(li - 1)  # one layer ahead of the compute
            p = params[li]
            lp = plan.layers[li]
            g = gh * nm[:, None]
            if li < L - 1:
                packed_mask = reader.get_mask(li)
                with jax.named_scope(scopes.RELU_MASK):
                    m = packmod.unpack(packed_mask, 1, lp.mask_elems)
                    g = g * m.reshape(g.shape).astype(g.dtype)
            # transpose of the output-side Â product (gcn applies it
            # after the linear)
            gz = g
            if not sage:
                with jax.named_scope(scopes.AGG_BWD):
                    gz = _spmm(g, gcn_adj.T)
            g2 = gz.reshape(-1, gz.shape[-1])
            saved = (reader.get_raw(li) if lp.cfg is None
                     else reader.get_ct(li))
            with jax.named_scope(scopes.DENSE):
                if lp.cfg is None:
                    dw = saved.reshape(-1, saved.shape[-1]).T @ g2
                else:
                    # fused path: stash dequantized in the backward
                    # matmul's prologue (no f32 reconstruction round-trips
                    # HBM)
                    dw = decompress_matmul(saved, g2, fused=fused)
                dparams[li] = {"w": dw.astype(p["w"].dtype),
                               "b": jnp.sum(gz, axis=0).astype(p["b"].dtype)}
                gx = (gz @ p["w"].T).astype(saved.dtype)
            if sage:
                d = gx.shape[1] // 2
                with jax.named_scope(scopes.AGG_BWD):
                    # gx[:, :d] + Âᵀ·gx[:, d:], each row's sum started
                    # at its direct term, as autodiff's scatter takes it
                    gh = mean_adj.T.add_aggregate(gx[:, :d], gx[:, d:])
            else:
                gh = gx
        dfeats = gh * nm[:, None]
        return (dparams, dfeats, zero_cotangent(gcn_adj),
                zero_cotangent(mean_adj), np.zeros((), jax.dtypes.float0),
                jnp.zeros_like(nm))

    f.defvjp(f_fwd, f_bwd)
    return f


def stash_gnn_forward(params, graph, cfg, plan: StashPlan,
                      stash: StashPolicy = TENSOR_STASH, seed=0,
                      node_mask=None, fused: str = "auto"):
    """The engine's forward: ``gnn_forward`` values with the layer stashes
    routed through ``stash``'s writer (per-tensor or pooled arena)."""
    if len(plan.layers) != cfg.n_layers:
        raise ValueError(f"plan has {len(plan.layers)} layers for a "
                         f"{cfg.n_layers}-layer model")
    feats, gcn_adj, mean_adj = graph
    nm = (jnp.ones((feats.shape[0],), feats.dtype) if node_mask is None
          else node_mask.astype(feats.dtype))
    fn = _build(cfg, plan, stash, fused)
    return fn(params, feats, gcn_adj, mean_adj,
              jnp.asarray(seed, jnp.uint32), nm)


def arena_gnn_forward(params, graph, cfg, plan: StashPlan, seed=0,
                      node_mask=None, policy: str = "device"):
    """Drop-in for :func:`repro.graph.models.gnn_forward` with the stash
    pooled into an arena under the given offload policy (the legacy
    arena-only spelling of :func:`stash_gnn_forward`)."""
    stash_engine.check_policy(policy)
    return stash_gnn_forward(params, graph, cfg, plan,
                             StashPolicy(kind="arena", placement=policy),
                             seed=seed, node_mask=node_mask)


# --------------------------------------------------------- mesh forward
def mesh_stash_plan(cfg, in_dim: int, n_local: int) -> StashPlan:
    """Halo-aware stash planning for the mesh lowering: the plan of ONE
    device's saved-for-backward bytes.

    Every stash the mesh forward creates is partition-local —
    ``compressed_matmul`` compresses the local ``(n_local, d)`` linear
    input (halo rows feed only the *aggregation*, whose VJP needs no
    float activations), and the ReLU sign mask covers local rows only.
    So the per-device plan is exactly the single-device plan at the
    partition's padded node count: the halo strip contributes zero stash
    bytes by construction.  This ledger backs the mesh arm of
    ``activation_memory_report`` and the ≥2x per-device peak gate in
    ``BENCH_gnn_dist.json``.
    """
    return plan_gnn_stashes(cfg, in_dim, n_local)


def mesh_gnn_forward(params, feats, esrc, edst, gcn_w, mean_w, nm, send_idx,
                     cfg, *, seed, axis: str | None = "graph"):
    """One device's slice of the mesh-sharded GNN forward.

    The same per-layer math as :func:`repro.graph.models.gnn_forward`
    composed from the per-op ``custom_vjp`` stack (``compressed_matmul``,
    ``relu_1bit``) and ``spmm`` over edge lists — bit-identical gradients
    to the engine's
    stash forward per the PR 5 parity gate — with one addition: before
    each aggregation, :func:`repro.parallel.halo.halo_exchange` extends
    the aggregated tensor with the round-mates' boundary rows.  GCN
    exchanges the biased pre-aggregation output (receivers need the
    sender's full ``x @ w + b`` value); SAGE exchanges ``h`` ahead of its
    input-side mean aggregation.  Edge tables come pre-extended from
    :func:`repro.parallel.halo.build_halo_program`; ``axis=None`` (or a
    zero halo width) runs the identical single-device computation.

    Only local activations are ever stashed for backward — see
    :func:`mesh_stash_plan`.
    """
    from repro.core.act_compress import compressed_matmul
    from repro.graph.models import relu_1bit, spmm
    from repro.parallel.halo import halo_exchange

    per_layer = cfg.layer_compression()
    n = feats.shape[0]
    gcn_adj = EdgeList(esrc, edst, gcn_w, n)
    mean_adj = EdgeList(esrc, edst, mean_w, n)
    seed = jnp.asarray(seed, jnp.uint32)
    h = feats * nm[:, None]
    for li, p in enumerate(params):
        lseed = seeds.layer_seed(seed, li)
        comp = per_layer[li]
        if cfg.arch == "gcn":
            z = (h @ p["w"] if comp is None
                 else compressed_matmul(h, p["w"], lseed, comp)) + p["b"]
            z = spmm(halo_exchange(z, send_idx, axis), gcn_adj)
        else:  # sage
            agg = spmm(halo_exchange(h, send_idx, axis), mean_adj)
            x = jnp.concatenate([h, agg], axis=1)
            z = (x @ p["w"] if comp is None
                 else compressed_matmul(x, p["w"], lseed, comp)) + p["b"]
        if li < len(params) - 1:
            z = relu_1bit(z)
        h = z * nm[:, None]
    return h
