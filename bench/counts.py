"""Operations and bytes, computed from shapes.

``model_flops`` is the rule ``step_mfu`` uses: the dense matmuls a
GraphSAGE epoch needs, forward plus the weight and input gradients, with
no input gradient at layer 0 (its input is the fixed features).
Aggregation, compression and random projection do not count.

``kernel_cost`` is the least work of one Pallas kernel call, from the
shapes the compiled program gives it: every operand read once and every
result written once, except the fused backward, whose least output is
one ``(D, N)`` weight gradient however many partials it writes.
"""
from __future__ import annotations

import math

_ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8}


def model_flops(n_nodes: int, dims: tuple[int, ...]) -> int:
    """Dense matmul FLOPs of one full-graph GraphSAGE epoch: layer ``i``
    multiplies (N, 2 * dims[i]) by (2 * dims[i], dims[i + 1])."""
    total = 0
    for li, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        mm = 2 * n_nodes * (2 * d_in) * d_out
        total += mm * (2 if li == 0 else 3)   # forward, dW[, dX]
    return total


def shape_bytes(dtype: str, dims: tuple[int, ...]) -> int:
    return _ITEMSIZE[dtype] * math.prod(dims)


def kernel_cost(kind: str, operands: list[tuple[str, tuple[int, ...]]],
                results: list[tuple[str, tuple[int, ...]]]
                ) -> tuple[int, int]:
    """(FLOPs, bytes) of one call of a kernel of ``kind``:

    * ``quant`` — f32 blocks in; packed codes, zero and range out;
    * ``dequant`` — packed codes, zero and range in; f32 blocks out;
    * ``fused_fwd`` — ``y = x @ w`` with ``x`` quantized in the epilogue:
      x and w in, y and the codes out; 2*M*D*N FLOPs;
    * ``fused_bwd`` — ``dw = dequant(codes)^T @ g``: codes and g in, one
      (D, N) f32 gradient out; 2*M*D*N FLOPs.

    Scalar operands (the seed) are not counted.
    """
    ins = sum(shape_bytes(t, s) for t, s in operands if math.prod(s) > 1)
    outs = sum(shape_bytes(t, s) for t, s in results)
    if kind in ("quant", "dequant"):
        return 0, ins + outs
    if kind == "fused_fwd":
        (_, (m, d)), (_, (d2, n)) = [o for o in operands if len(o[1]) == 2
                                     and math.prod(o[1]) > 1][:2]
        if d != d2:
            raise ValueError(f"fused_fwd operands {operands} do not chain")
        return 2 * m * d * n, ins + outs
    if kind == "fused_bwd":
        m, n = operands[-1][1]
        dn = results[0][1][-2:]
        d = dn[0]
        if dn[1] != n:
            raise ValueError(f"fused_bwd result {results} does not match "
                             f"g of shape {(m, n)}")
        return 2 * m * d * n, ins + 4 * d * n
    raise ValueError(f"unknown kernel kind {kind!r}")
