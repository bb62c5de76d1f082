"""Instrumentation for the paper's Table 2 / Fig. 2 / Fig. 4-5 analyses:
observed activation distributions, JS divergences against the uniform and
clipped-normal models, and empirical SR variance reduction (Eq. 19).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import pack as packmod
from repro.core import quant as quantmod
from repro.core import random_projection as rpmod
from repro.core.autoprec import LayerStats
from repro.core.variance import js_divergence, model_histogram, optimize_levels
from repro.engine.seeds import layer_seed
from repro.graph.models import GNNConfig, _dims, spmm


def relu_mask_nbytes(n_elements: int) -> int:
    """Bytes of the packed 1-bit ReLU sign mask for ``n_elements`` values.

    :func:`repro.graph.models.relu_1bit` packs the flattened tensor into
    whole uint32 words, so the count is word-aligned ceil — plain
    ``n // 8`` floor-divides away the partial word when the element count
    isn't 32-aligned.
    """
    return 4 * ((n_elements + 31) // 32)


def saved_bytes_per_layer(cfg: GNNConfig, in_dim: int,
                          n_nodes: int) -> list[dict]:
    """Per-layer saved-for-backward bytes under the paper's Table-1 model.

    One row per GNN layer: ``fp32_bytes`` is the f32 linear input plus (on
    hidden layers) the f32 ReLU context; ``compressed_bytes`` (only on
    layers with a compression config) is the packed post-RP code words +
    8-byte per-block (zero, range) pairs + the word-aligned 1-bit ReLU sign
    mask, and ``bits`` names the layer's quantization width so
    mixed-precision (autoprec) breakdowns read directly off the rows.
    ``n_nodes`` is whatever node count is live at once — the full graph, or
    one padded subgraph batch in the mini-batch regime (this is what makes
    the same model serve :func:`repro.graph.train.activation_memory_report`
    in both modes).
    """
    dims = _dims(cfg, in_dim)
    per_layer = cfg.layer_compression()
    rows = []
    for li, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        lin_in = d_in * (2 if cfg.arch == "sage" else 1)
        hidden = li < len(dims) - 2
        fp32 = n_nodes * lin_in * 4 + (n_nodes * d_out * 4 if hidden else 0)
        row = {"layer": li, "fp32_bytes": fp32}
        comp = per_layer[li]
        if comp is not None:
            d_eff = lin_in // comp.rp_ratio if comp.rp_ratio > 1 else lin_in
            # + 4: the uint32 rp_seed scalar every CompressedTensor stores
            # (CompressedTensor.nbytes counts it, so the model must too)
            c = packmod.packed_nbytes((n_nodes, d_eff), comp.bits,
                                      comp.group_size) + 4
            if hidden:
                c += relu_mask_nbytes(n_nodes * d_out)  # 1-bit ReLU mask
            row["compressed_bytes"] = c
            row["bits"] = comp.bits
        rows.append(row)
    return rows


def _iter_layer_inputs(params, graph, cfg: GNNConfig):
    """Yield ``(li, x)`` where ``x`` is the linear input layer li stashes.

    The single inference-mode traversal shared by every analysis collector
    (:func:`collect_layer_stats`, :func:`collect_projected_activations`),
    mirroring :func:`repro.graph.models.gnn_forward` — arch dispatch, sage
    concat, Â aggregation, interior ReLU — so the collectors cannot drift
    from what training actually saves.
    """
    feats, gcn_adj, mean_adj = graph
    h = feats
    for li, p in enumerate(params):
        if cfg.arch == "gcn":
            x = h
        else:
            agg = spmm(h, mean_adj)
            x = jnp.concatenate([h, agg], axis=1)
        yield li, x
        z = x @ p["w"] + p["b"]
        if cfg.arch == "gcn":
            z = spmm(z, gcn_adj)
        if li < len(params) - 1:
            z = jnp.maximum(z, 0.0)
        h = z


def collect_layer_stats(params, graph, cfg: GNNConfig,
                        seed: int = 0) -> list[LayerStats | None]:
    """One forward pass collecting the allocator's per-layer sensitivities.

    For every compressed layer this captures exactly what
    ``compressed_matmul`` would stash — the linear input, post-RP at the
    layer's own ``rp_ratio`` and the forward pass's RP seed derivation,
    regrouped into the layer's quantization blocks — and summarizes it as
    a :class:`repro.core.autoprec.LayerStats` (stash shape, block count,
    E[range²]).  Uncompressed layers yield ``None``.  Cheap by design:
    moments only, no quantization, no grads — run it on the first epoch's
    params and refresh every few epochs.
    """
    per_layer = cfg.layer_compression()
    stats: list[LayerStats | None] = []
    for li, x in _iter_layer_inputs(params, graph, cfg):
        comp = per_layer[li]
        if comp is None:
            stats.append(None)
            continue
        xs = x
        if comp.rp_ratio > 1:
            # the same seed derivation gnn_forward -> compress uses
            rp_seed = layer_seed(seed, li) ^ jnp.uint32(0xA5A5_A5A5)
            xs = rpmod.rp(x, rp_seed, max(1, x.shape[1] // comp.rp_ratio))
        blocks, _ = quantmod.group_reshape(xs, comp.group_size)
        _, rng = quantmod.block_stats(blocks)
        stats.append(LayerStats(
            shape=tuple(int(s) for s in xs.shape),
            n_blocks=int(blocks.shape[0]),
            rng_sq_mean=float(jnp.mean(rng.astype(jnp.float32) ** 2))))
    return stats


def variance_validation_report(params, graph, cfg: GNNConfig,
                               seed: int = 0) -> list[dict]:
    """Measured SR dequantization variance vs the Eq. 10 prediction, one
    row per compressed layer.

    Runs the obs telemetry probe (:mod:`repro.obs.quantstats`) on
    ``params`` — the same quantize→dequantize the training stash performs,
    same per-layer seed scheme — and prices the layer's
    :class:`~repro.core.autoprec.LayerStats` through
    :func:`repro.core.autoprec.expected_layer_variance`.  Rows carry
    ``measured_var`` / ``predicted_var`` / ``ratio`` / ``sat_rate``; a
    ratio far from 1 on a real layer means the variance model the
    autoprec allocator prices with has drifted from what the quantizer
    does.
    """
    # obs.quantstats reaches back into this module for _iter_layer_inputs
    # (lazily, inside the probe) — import at call time, not module load
    from repro.obs.quantstats import health_rows, measure_quant_health

    measured = measure_quant_health(params, graph, cfg, seed=seed)
    return health_rows(measured, cfg.layer_compression())


def collect_projected_activations(params, graph, cfg: GNNConfig,
                                  rp_ratio: int = 8, seed: int = 0,
                                  bits: int = 2):
    """Forward pass capturing each layer's *normalized projected* activation
    H̄_proj (paper App. D: saved after RP, before quantization, normalized
    per row to [0, B] with B = 2**bits − 1)."""
    B = float(2**bits - 1)
    captured = []
    for li, x in _iter_layer_inputs(params, graph, cfg):
        r_dim = max(1, x.shape[1] // rp_ratio)
        proj = rpmod.rp(x, jnp.uint32(seed + li), r_dim)
        zero = proj.min(axis=1, keepdims=True)
        rng = jnp.maximum(proj.max(axis=1, keepdims=True) - zero,
                          quantmod.EPS)
        captured.append(np.asarray((proj - zero) / rng * B))
    return captured


def table2_row(hbar: np.ndarray, bits: int = 2, n_bins: int = 60) -> dict:
    """JS(uniform), JS(clipped-normal), empirical VM variance reduction."""
    R = hbar.shape[1]
    B = 2**bits - 1
    edges = np.linspace(0, B, n_bins + 1)
    obs, _ = np.histogram(hbar.reshape(-1), bins=edges)
    obs = obs / obs.sum()
    js_u = js_divergence(obs, model_histogram(R, bits, edges, "uniform"))
    js_cn = js_divergence(obs, model_histogram(R, bits, edges, "clipnorm"))

    # Eq. 19: Var.Red = 1 − Σ(h̄ − ⌊h̄⌉*)² / Σ(h̄ − ⌊h̄⌉)²
    h = jnp.asarray(hbar)
    lv_o = jnp.asarray(optimize_levels(R, bits), jnp.float32)
    err_u, err_o, n_rep = 0.0, 0.0, 4
    for s in range(n_rep):
        cu = quantmod.stochastic_round_to_levels(h, quantmod.uniform_levels(bits), s)
        co = quantmod.stochastic_round_to_levels(h, lv_o, s + 101)
        du = jnp.take(quantmod.uniform_levels(bits), cu)
        do = jnp.take(lv_o, co)
        err_u += float(jnp.sum((h - du) ** 2))
        err_o += float(jnp.sum((h - do) ** 2))
    return {
        "R": R,
        "js_uniform": float(js_u),
        "js_clipnorm": float(js_cn),
        "var_reduction_pct": 100.0 * (1.0 - err_o / max(err_u, 1e-30)),
    }
