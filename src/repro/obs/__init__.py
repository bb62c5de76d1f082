"""Runtime observability: tracing, metrics, quantization-health telemetry.

Three layers, all default-off and all read-only taps (obs-on training is
bit-identical to obs-off — gated in ``tests/test_obs.py``):

* :mod:`~repro.obs.trace` — nested spans (plan compile, epochs, mesh
  rounds, autoprec re-solves, pager fetch waits), exported as Chrome
  ``trace_event`` JSON (Perfetto-loadable) and, inside a
  ``jax.profiler`` trace, also on its host plane; plus the repo-wide
  :func:`~repro.obs.trace.stopwatch` timing idiom;
* :mod:`~repro.obs.scopes` — the names of the hot path's layers, opened
  as ``jax.named_scope`` in the compiled step (device traces carry
  them);
* :mod:`~repro.obs.metrics` — counters / gauges / windowed histograms
  with shared null singletons when disabled (arena occupancy, pager
  overlap, halo bytes, recompile counts);
* :mod:`~repro.obs.quantstats` — the opt-in per-layer in-graph stats
  channel: measured SR dequantization variance, range moments and
  saturation rate per layer, shipped through one batched
  ``jax.debug.callback`` and reported side-by-side with the Eq. 10
  prediction; doubles as the ``calibration="obs"`` source for autoprec.

:class:`~repro.obs.policy.ObsPolicy` composes it all onto
:class:`~repro.engine.plan.ExecutionPlan` as the fifth policy;
:class:`~repro.obs.session.ObsSession` is one run's bundle of the three.

Import shape: policy/trace/metrics are stdlib-only and load eagerly
(``engine.plan`` pulls :class:`ObsPolicy` at import time); the
jax-facing session/quantstats modules resolve lazily via PEP 562.
"""
from __future__ import annotations

import importlib

from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, get_metrics, set_metrics)
from repro.obs.policy import ObsPolicy  # noqa: F401
from repro.obs.trace import (Span, Tracer, get_tracer,  # noqa: F401
                             set_tracer, span, stopwatch)

_LAZY = {
    "ObsSession": "repro.obs.session",
    "NULL_SESSION": "repro.obs.session",
    "QuantHealthMonitor": "repro.obs.quantstats",
    "measure_quant_health": "repro.obs.quantstats",
    "health_rows": "repro.obs.quantstats",
    "measured_sensitivity": "repro.obs.quantstats",
    "tap": "repro.obs.quantstats",
}

__all__ = ["ObsPolicy", "Tracer", "Span", "span", "stopwatch", "set_tracer",
           "get_tracer", "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "get_metrics", "set_metrics", *_LAZY]


def __getattr__(name: str):
    if name in _LAZY:
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
