"""The numbers that decide ``correct`` for a training cell.

The program's first three steps (driven by the window's own compiled
step) are compared with the plain reference's first three, from the same
graph and weights:

* ``loss1_gap`` — the relative gap between the two first-step losses
  (the forward pass and the loss, before any update);
* ``loss_gap`` — the largest relative gap between the two losses over
  the three steps;
* ``grad_gap`` — the first gradient as the optimizer got it (its first
  moment after one step over ``1 - b1``): the worst leaf's gap between
  the program's norm and the reference's;
* ``grad_gap_bias`` — the same over the bias leaves alone, whose
  gradient (a sum of the layer's output gradient) no activation
  compression estimates;
* ``grad_scale_gap`` — the same over the weight leaves, with the
  reference's norm scaled by what a random projection (RP) of ratio
  ``rp`` adds: the program's weight gradient is ``P G`` with one
  Rademacher ``P = R R^T / r`` (``D x r``, ``r = D / rp``) shared by all
  rows, and ``E |P G|^2 = (1 + (D - 1) / r) |G|^2``.  It reads the
  compression route's scale, which ``grad_gap`` cannot where RP noise
  dominates.  Where each of ``m`` chips draws its own ``P_i`` for its
  rows' share ``G_i`` and the shares are summed, ``E |sum P_i G_i|^2 =
  |G|^2 + (D - 1) / r * sum |G_i|^2``: the reference then gives the
  shares' norms (``chip_grad_norms``);
* ``update_gap`` — the parameters' change over the three updates: the
  worst leaf's gap between the two norms.

A leaf's gap is taken against the reference's norm of that leaf or of
the median leaf, whichever is larger.  Leaves whose reference gradient
is under a thousandth of the median leaf's move by round-off alone and
are left out.  A cell compares the numbers its file gives limits for,
each set from the readings kept beside it.
"""
from __future__ import annotations

import numpy as np

#: A leaf whose reference gradient norm is below this share of the median
#: leaf's is rounding noise, not signal.
NOUGHT_SHARE = 1e-3


def _leaves(tree) -> list[np.ndarray]:
    """Leaves in (layer, key) order: ``b`` then ``w`` of each layer."""
    out = []
    for layer in tree:
        for k in sorted(layer):
            out.append(np.asarray(layer[k], np.float64))
    return out


def _is_bias(tree) -> list[bool]:
    return [k == "b" for layer in tree for k in sorted(layer)]


def _leaf_gaps(got: list, ref: list, counted: list[bool]) -> list[float]:
    """Each leaf's norm gap; ``nan`` for a leaf that is not counted."""
    gn = np.array([np.linalg.norm(a) for a in got])
    rn = np.array([np.linalg.norm(a) for a in ref])
    floor = np.median(rn[counted])
    gaps = np.abs(gn - rn) / np.maximum(rn, floor)
    return [float(x) if c else float("nan") for x, c in zip(gaps, counted)]


def details(prog: dict, ref: dict, params0) -> dict:
    """Every reading, step by step and leaf by leaf (leaves in
    ``(layer, b/w)`` order).  ``prog`` and ``ref`` each hold ``losses``
    (3 floats), ``grad`` (the first gradient, a params-shaped tree) and
    ``params3`` (after three updates); ``params0`` is the common start."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g_ref = _leaves(ref["grad"])
    rn = np.array([np.linalg.norm(a) for a in g_ref])
    counted = list(rn >= NOUGHT_SHARE * np.median(rn))
    p0 = _leaves(params0)
    d_prog = [a - b for a, b in zip(_leaves(prog["params3"]), p0)]
    d_ref = [a - b for a, b in zip(_leaves(ref["params3"]), p0)]
    g_prog = _leaves(prog["grad"])
    norms = lambda xs: [float(np.linalg.norm(a)) for a in xs]
    return {"loss_gaps": [float(x) for x in np.abs(lp - lr) / np.abs(lr)],
            "grad_gaps": _leaf_gaps(g_prog, g_ref, counted),
            "update_gaps": _leaf_gaps(d_prog, d_ref, counted),
            "counted": [bool(c) for c in counted],
            "grad_norms": [norms(g_prog), norms(g_ref)],
            "update_norms": [norms(d_prog), norms(d_ref)],
            "grad_cos": [float(np.vdot(a, b) / max(np.linalg.norm(a)
                                                  * np.linalg.norm(b),
                                                  1e-30))
                         for a, b in zip(g_prog, g_ref)]}


def rp_scale(rows: int, rp: int, share: float = 1.0) -> float:
    """The expected norm ratio ``sqrt(1 + (D - 1) / r * share)`` that an
    RP of ratio ``rp`` over ``D = rows`` gives a weight gradient; 1
    without.  ``share`` is ``sum |G_i|^2 / |G|^2`` over chips that draw
    their projections apart (1 for one projection)."""
    if rp <= 1:
        return 1.0
    return float(np.sqrt(1 + (rows - 1) / (rows // rp) * share))


def _shares(ref: dict, rn, params0) -> list[float]:
    """``sum |G_i|^2 / |G|^2`` of each weight leaf, from the reference's
    ``chip_grad_norms`` (one list of per-chip norms a layer) where it
    has them; 1 each otherwise."""
    parts = ref.get("chip_grad_norms")
    weights = [i for i, a in enumerate(_leaves(params0)) if a.ndim == 2]
    if parts is None:
        return [1.0] * len(weights)
    return [float(np.sum(np.square(p)) / max(rn[i] ** 2, 1e-30))
            for p, i in zip(parts, weights)]


def readings(prog: dict, ref: dict, params0, rp: int = 0) -> dict:
    """The numbers a cell's limits may name (see the module's doc); ``rp``
    is the cell's RP ratio (0: none).  A non-finite leaf makes its number
    non-finite."""
    d = details(prog, ref, params0)
    counted = np.array(d["counted"])
    is_bias = np.array(_is_bias(params0))
    bias, weight = is_bias & counted, ~is_bias & counted
    grad, upd = np.array(d["grad_gaps"]), np.array(d["update_gaps"])
    gn, rn = (np.array(x) for x in d["grad_norms"])
    shares = iter(_shares(ref, rn, params0))
    scale = np.array([rp_scale(a.shape[0], rp, next(shares))
                      if a.ndim == 2 else 1.0 for a in _leaves(params0)])
    floor = np.median(rn[counted])
    scaled = np.abs(gn - scale * rn) / np.maximum(scale * rn, floor)
    return {"loss1_gap": d["loss_gaps"][0],
            "loss_gap": float(np.max(d["loss_gaps"])),
            "grad_gap": float(np.max(grad[counted])),
            "grad_gap_bias": float(np.max(grad[bias])),
            "grad_scale_gap": float(np.max(scaled[weight])),
            "update_gap": float(np.max(upd[counted]))}


def judge(read: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}); a missing or non-finite
    reading is not correct."""
    checks, ok = {}, True
    for k in limits:
        v = read.get(k)
        checks[k] = {"value": v, "limit": limits[k]}
        ok = ok and v is not None and np.isfinite(v) and v <= limits[k]
    return bool(ok), checks
