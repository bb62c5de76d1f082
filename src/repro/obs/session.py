"""ObsSession: one run's observability state, built from the plan's
:class:`~repro.obs.policy.ObsPolicy`.

Bundles the tracer, the metrics registry, and the quant-health monitor;
``activate()`` installs the tracer/registry as the process-wide actives
(so producers without a session handle — the forward builder,
benchmark stopwatches — land in the same sinks) and restores
the previous ones on exit.  The shared :data:`NULL_SESSION` serves every
disabled run: all of its span/metric methods are no-ops, so the engine
instruments unconditionally.
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib

from repro.obs import metrics as metricsmod
from repro.obs import trace as tracemod
from repro.obs.metrics import (NULL_COUNTER, NULL_GAUGE, NULL_HISTOGRAM,
                               MetricsRegistry)
from repro.obs.policy import ObsPolicy
from repro.obs.trace import Tracer

_NULL_CM = contextlib.nullcontext()


class ObsSession:
    def __init__(self, policy: ObsPolicy):
        self.policy = policy
        self.enabled = policy.enabled
        self.tracer: Tracer | None = (Tracer() if policy.enabled
                                      and policy.trace else None)
        self.registry: MetricsRegistry | None = (
            MetricsRegistry() if policy.enabled and policy.metrics else None)
        self._quant = None

    @classmethod
    def from_policy(cls, policy: ObsPolicy | None) -> "ObsSession":
        if policy is None or not policy.enabled:
            return NULL_SESSION
        return cls(policy)

    # ------------------------------------------------------------ lifetime
    @contextlib.contextmanager
    def activate(self):
        """Install this session's tracer/registry as the process actives
        for the duration (restoring the previous ones after)."""
        prev_t = tracemod.set_tracer(self.tracer) if self.tracer else None
        prev_m = (metricsmod.set_metrics(self.registry)
                  if self.registry else None)
        try:
            yield self
        finally:
            if self.tracer is not None:
                tracemod.set_tracer(prev_t)
            if self.registry is not None:
                metricsmod.set_metrics(prev_m)

    # --------------------------------------------------------------- spans
    def span(self, name: str, **args):
        return (self.tracer.span(name, **args) if self.tracer is not None
                else _NULL_CM)

    # ------------------------------------------------------------- metrics
    def counter(self, name: str):
        return (self.registry.counter(name) if self.registry is not None
                else NULL_COUNTER)

    def gauge(self, name: str):
        return (self.registry.gauge(name) if self.registry is not None
                else NULL_GAUGE)

    def histogram(self, name: str, window: int = 64):
        return (self.registry.histogram(name, window=window)
                if self.registry is not None else NULL_HISTOGRAM)

    # -------------------------------------------------------- quant health
    def quant_due(self, epoch: int) -> bool:
        p = self.policy
        return (p.enabled and p.quant_stats
                and epoch % p.quant_stats_every == 0)

    def quant_probe(self, params, gt, epoch: int, cfg) -> None:
        """Run the telemetry probe (rebuilt when autoprec swaps cfg)."""
        from repro.obs.quantstats import QuantHealthMonitor

        if self._quant is None or self._quant.cfg != cfg:
            self._quant = QuantHealthMonitor(cfg)
        self._quant.probe(params, gt, epoch)

    def quant_rows(self) -> list[dict]:
        return self._quant.rows() if self._quant is not None else []

    # -------------------------------------------------------------- export
    def export(self, base_path) -> dict:
        """Write the trace as ``<base>.trace.json`` (Chrome
        ``trace_event``, loads directly in Perfetto); returns its path."""
        if self.tracer is None:
            return {}
        p = pathlib.Path(base_path)
        if p.suffix == ".json":
            p = p.with_suffix("")
        p.parent.mkdir(parents=True, exist_ok=True)
        chrome = p.with_suffix(".trace.json")
        self.tracer.export_chrome(chrome)
        return {"chrome": str(chrome)}

    def summary(self) -> dict:
        out: dict = {"policy": dataclasses.asdict(self.policy)}
        if self.tracer is not None:
            out["n_spans"] = len(self.tracer.spans)
        if self.registry is not None:
            out["metrics"] = self.registry.snapshot()
        if self._quant is not None:
            out["quant_health"] = self.quant_rows()
        return out


#: The shared disabled session every obs-off run binds.
NULL_SESSION = ObsSession(ObsPolicy())
