"""Unified observability layer (DESIGN.md §13).

- ``obs.trace``   — nested spans, injectable clock, zero-cost disabled path
- ``obs.metrics`` — counters / bounded-reservoir histograms
- ``obs.export``  — Chrome trace-event + phase-aggregate exporters
- ``obs.logs``    — the standardized ``training_logs`` schema
- ``obs.clock``   — the sanctioned timing sources for all of ``src/``
"""
from . import clock, export, logs, metrics, trace
from .export import chrome_trace, phase_summary, profile_dict, \
    write_chrome_trace
from .logs import build_training_logs, summarize_training_logs, \
    validate_training_logs
from .metrics import Counter, Histogram, MetricsRegistry
from .trace import Span, Tracer, capture, enabled, event, span

__all__ = [
    "clock", "export", "logs", "metrics", "trace",
    "chrome_trace", "phase_summary", "profile_dict", "write_chrome_trace",
    "build_training_logs", "summarize_training_logs",
    "validate_training_logs",
    "Counter", "Histogram", "MetricsRegistry",
    "Span", "Tracer", "capture", "enabled", "event", "span",
]
