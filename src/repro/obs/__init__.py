"""Observability layer: request span tracing, engine phase spans, step
timeline and Prometheus exposition (DESIGN.md §13).

The observer is off by default and near-free when disabled: the engine's
hot loop checks one attribute (``engine.observer is None``). Always on:
the engine's phase spans (``spans.Phases``: a profiler annotation and a
counter per phase, a few microseconds each), the driver's latency
histograms and the ``/metrics`` text renderer, the latter two off the hot
path (per *finished request*, per scrape).
"""
from repro.obs.numerics import NumericsObserver, validate_train_trace
from repro.obs.observer import EngineObserver
from repro.obs.prom import (Histogram, parse_prometheus_text,
                            render_prometheus)
from repro.obs.spans import Phases, SpanRing, validate_chrome_trace
from repro.obs.timeline import StepTimeline

__all__ = [
    "EngineObserver", "NumericsObserver", "Phases", "SpanRing",
    "StepTimeline", "Histogram", "render_prometheus",
    "parse_prometheus_text", "validate_chrome_trace", "validate_train_trace",
]
