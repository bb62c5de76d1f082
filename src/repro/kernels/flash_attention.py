"""Flash attention (Pallas, TPU target) — §Perf cell-B3 follow-up.

The roofline analysis showed prefill_32k memory terms dominated by
attention-score HBM traffic (S² tiles materialized by the pure-XLA online
softmax under CPU-backend fusion).  This kernel keeps the (BLK_Q, BLK_K)
score tile and the running (m, l, acc) statistics in VMEM scratch across
the K-block grid dimension, so score traffic never reaches HBM — the
classic FlashAttention dataflow mapped to MXU tiles.

Grid: (B·H, Sq/BLK_Q, Skv/BLK_K), K innermost.  Causal masking by global
block indices; fully-masked K blocks are skipped via ``pl.when``.
Validated in interpret mode against ``ref.py``'s softmax oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, blk_q: int, blk_k: int, n_k: int, causal: bool,
                  scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(1)
    # with causal masking, K blocks strictly above the diagonal contribute
    # nothing — skip their compute entirely
    live = (not causal) or (ki * blk_k < (qi + 1) * blk_q)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)                 # (blk_q, dh)
        k = k_ref[0].astype(jnp.float32)                 # (blk_k, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                    # (blk_q, blk_k)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + qi * blk_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                + ki * blk_k
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_call(q, k, v, *, causal: bool = True,
                         blk_q: int = 128, blk_k: int = 128,
                         interpret: bool = False):
    """q (BH, Sq, Dh), k/v (BH, Skv, Dh) — heads pre-flattened into BH.

    Sq % blk_q == 0 and Skv % blk_k == 0 (pad outside).
    """
    bh, sq, dh = q.shape
    skv = k.shape[1]
    assert sq % blk_q == 0 and skv % blk_k == 0, (sq, skv)
    n_q, n_k = sq // blk_q, skv // blk_k
    scale = 1.0 / np.sqrt(dh)
    kern = functools.partial(_flash_kernel, blk_q=blk_q, blk_k=blk_k,
                             n_k=n_k, causal=causal, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            # (blk_q, 1) running max / denom and (blk_q, dh) accumulator,
            # carried in VMEM across the K-block grid dimension
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
