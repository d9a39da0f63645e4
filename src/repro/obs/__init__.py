"""Observability: span tracing, metrics, Perfetto export, device timing.

The subsystem is deliberately import-light: no module here imports
``repro.core`` at module level, so core modules (cache, ledger) may
import :mod:`repro.obs.metrics` at the top of the file without creating
a cycle.  The :class:`TraceRecorder` reaches back into
``repro.core.profiling`` only at install time (``__enter__``) to wire
itself in as the file exporter behind the ``phase()`` span seam.
"""

from .attribution import (AttributedOp, AttributionReport, Roofs, attribute,
                          attribution_from_static, roofs_from_trials)
from .device_timing import (DeviceOps, DeviceTiming,
                            device_timing_available, profile_ops,
                            profile_sample)
from .export import (load_events, to_chrome_trace, trial_summaries,
                     validate_chrome_trace, write_chrome_trace)
from .metrics import MetricsRegistry, metrics
from .trace import TRACE_VERSION, TraceRecorder, recorder

__all__ = [
    "AttributedOp",
    "AttributionReport",
    "DeviceOps",
    "DeviceTiming",
    "MetricsRegistry",
    "Roofs",
    "TRACE_VERSION",
    "TraceRecorder",
    "attribute",
    "attribution_from_static",
    "device_timing_available",
    "load_events",
    "metrics",
    "profile_ops",
    "profile_sample",
    "recorder",
    "roofs_from_trials",
    "to_chrome_trace",
    "trial_summaries",
    "validate_chrome_trace",
    "write_chrome_trace",
]
