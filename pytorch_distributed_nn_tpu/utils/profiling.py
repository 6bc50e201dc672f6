"""Tracing / profiling hooks — absorbed into :mod:`obs.xray`.

The primitives that used to live here (``xprof_trace`` capture,
``StepTimer``/``time_steps`` fenced wall timing, the perfetto
collective-slice parser, ``bus_bandwidth``) are now part of the Xray
subsystem (:mod:`pytorch_distributed_nn_tpu.obs.xray`), which adds
anomaly-triggered capture, per-op attribution, and compile telemetry
on top of them. This shim re-exports the original names so existing
imports (scripts, tests, notebooks) keep working unchanged.
"""

from __future__ import annotations

from pytorch_distributed_nn_tpu.obs.xray import (  # noqa: F401
    _COLLECTIVE_RE,
    BusBandwidth,
    CollectiveTrace,
    StepTimer,
    bus_bandwidth,
    collective_trace_seconds,
    time_steps,
    xprof_trace,
)

__all__ = [
    "BusBandwidth",
    "CollectiveTrace",
    "StepTimer",
    "bus_bandwidth",
    "collective_trace_seconds",
    "time_steps",
    "xprof_trace",
]
