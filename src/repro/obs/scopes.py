"""Names of the hot path's layers inside the compiled training step.

The program opens each of these as a ``jax.named_scope`` where the
layer's work happens; the name then rides every HLO instruction's
``op_name`` metadata into the optimized module and the device profile,
so a trace's device time can be put down to a layer by name instead of
by the shape of an op.  Scopes change metadata only: the compiled step
is op for op the same with or without them.

* ``agg_fwd`` / ``agg_bwd`` — the sparse aggregation ``Â·h`` (gather,
  edge-weight multiply, segment-sum) in the forward and its transpose in
  the backward (:mod:`repro.engine.forward`);
* ``act_compress`` — random projection, blocking and block-wise
  quantize + pack of a saved activation, or the whole fused
  matmul-quantize kernel (:mod:`repro.core.compressor`);
* ``act_decompress`` — unpack + dequantize and the inverse projection,
  or the whole fused dequantize-matmul kernel;
* ``relu_mask`` — the 1-bit ReLU sign mask: its pack in the forward and
  its unpack and use in the backward (:mod:`repro.engine.forward`,
  :func:`repro.graph.models.relu_1bit`);
* ``dense`` — the rest of a layer's transform: ``x @ w + b`` and the
  ReLU in the forward, the weight, bias and input gradients in the
  backward (:mod:`repro.engine.forward`).  The compression scopes nest
  inside it, and the innermost scope is an op's.

Stdlib only, so the benchmark can read traces with it and load no JAX.
"""
from __future__ import annotations

import re

AGG_FWD = "agg_fwd"
AGG_BWD = "agg_bwd"
ACT_COMPRESS = "act_compress"
ACT_DECOMPRESS = "act_decompress"
RELU_MASK = "relu_mask"
DENSE = "dense"

#: every scope the program opens
SCOPES = (AGG_FWD, AGG_BWD, ACT_COMPRESS, ACT_DECOMPRESS, RELU_MASK, DENSE)

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def _bare(segment: str) -> str:
    """A name-stack segment without its transform wrappers:
    ``transpose(jvp(agg_bwd))`` -> ``agg_bwd``."""
    m = _WRAPPED.match(segment)
    while m:
        segment = m.group(1)
        m = _WRAPPED.match(segment)
    return segment


def scope_of(op_name: str | None) -> str | None:
    """The innermost scope of :data:`SCOPES` among the path segments of an
    HLO ``op_name`` (``jit(step)/transpose(jvp(agg_bwd))/scatter-add`` ->
    ``agg_bwd``), or ``None``."""
    if not op_name:
        return None
    found = None
    for segment in op_name.split("/"):
        name = _bare(segment)
        if name in SCOPES:
            found = name
    return found
