"""Observability layer: schedule traces, blame attribution, exporters.

Always available, off by default.  Four tiers:

  * ``repro.obs.metrics`` — process-wide counters/gauges/histograms,
    gated by ``REPRO_OBS=1`` (no-ops otherwise; the engines' inner loops
    carry no obs code either way);
  * ``repro.obs.spans`` — host spans on the profiler's clock
    (``span(name, **args)``, a ``jax.profiler.TraceAnnotation``): they
    record only while a profiler session is active, so an xprof trace of
    ``plan()``, an engine call or a re-plan shows which phase the time
    went to, beside the device's operations;
  * ``repro.obs.trace`` / ``repro.obs.blame`` — post-hoc analysis of a
    recorded schedule: task/flow spans, NIC utilization timelines,
    critical-path blame decomposition that conserves the makespan;
  * ``repro.obs.perfetto`` / ``repro.obs.telemetry`` — exporters:
    Chrome/Perfetto ``trace.json`` and planner telemetry dicts.

``metrics`` and ``spans`` are imported eagerly (they have no intra-repro
dependencies and the core engines import them); the analysis modules load lazily on first
attribute access so ``repro.core -> repro.obs.metrics`` never cycles
through ``repro.obs.trace -> repro.core``.
"""
from __future__ import annotations

import importlib
from typing import Any

from .metrics import REGISTRY, MetricsRegistry, enabled  # noqa: F401
from .spans import next_seq, span  # noqa: F401

_LAZY = {
    "ScheduleTrace": ("trace", "ScheduleTrace"),
    "TaskSpan": ("trace", "TaskSpan"),
    "FlowSpan": ("trace", "FlowSpan"),
    "BlameReport": ("blame", "BlameReport"),
    "blame": ("blame", "blame"),
    "blame_by_tenant": ("blame", "blame_by_tenant"),
    "blame_delta": ("blame", "blame_delta"),
    "combine": ("blame", "combine"),
    "to_trace_events": ("perfetto", "to_trace_events"),
    "write_trace": ("perfetto", "write_trace"),
    "validate_trace_events": ("perfetto", "validate_trace_events"),
    "search_telemetry": ("telemetry", "search_telemetry"),
    "replan_telemetry": ("telemetry", "replan_telemetry"),
    "cache_telemetry": ("telemetry", "cache_telemetry"),
}

__all__ = ["REGISTRY", "MetricsRegistry", "enabled", "next_seq", "span", *_LAZY]


def __getattr__(name: str) -> Any:
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    mod = importlib.import_module(f".{mod_name}", __name__)
    value = getattr(mod, attr)
    globals()[name] = value
    return value
