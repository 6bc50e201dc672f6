"""Unified telemetry: metric registry, span tracing, goodput accounting.

One subsystem the whole stack reports into (ISSUE 1), replacing four
disconnected islands (JSONL logger, StepTimer, perfetto parsing, store
heartbeats) with:

- :mod:`obs.registry` — process-wide counters/gauges/histograms with
  Prometheus text exposition and the JSONL sink as backends;
- :mod:`obs.span` — ``with obs.span("data/next_batch"): ...`` Chrome
  trace events per host, free when disabled;
- :mod:`obs.goodput` — per-step wall-time decomposition into
  data/compute/collective/checkpoint/eval/other, and the serve loop's
  whole-thread tally (phases that partition its wall time, one record
  a round: :func:`serve_loop_records`);
- :mod:`obs.jitwatch` — the process's one ``jax.monitoring`` listener:
  which call traced, lowered or compiled, on which thread, and what
  the garbage collector held;
- :mod:`obs.runtime_gauges` — mesh topology + heartbeat state gauges;
- :mod:`obs.aggregate` — cross-host snapshot aggregation through the
  native store;
- :mod:`obs.flight` — the post-mortem flight recorder (ISSUE 2): a
  bounded per-host ring of collective/step/checkpoint/data events,
  dumped to ``flight_rank<k>.json`` on hangs/crashes;
- :mod:`obs.forensics` — cross-rank dump analysis (first divergent
  collective, hang/crash/straggler classification);
- :mod:`obs.stats` — shared stdlib-only percentile/median/MAD/EWMA
  helpers the reporting and detection layers agree on;
- :mod:`obs.watchtower` — online anomaly detection (ISSUE 7): streaming
  detectors over the metric/flight streams raising structured alerts
  (step-time outliers, loss spikes, straggler drift, queue/KV pressure,
  multi-window SLO burn rate), inert unless ``TPUNN_WATCH`` is set;
- :mod:`obs.capacity` — Skyline capacity frontier (ISSUE 11): sweep
  :mod:`serve.traffic` offered-load rungs against a fleet (or the
  deterministic service model), judge each rung with the watchtower's
  burn-rate signal, and emit the max-sustainable-rate frontier, the
  goodput-saturation knee, and the "replicas needed per SLO per
  traffic shape" planning report (``scripts/obs_report.py
  --capacity`` renders it);
- :mod:`obs.trace` — Causeway distributed request tracing (ISSUE 16):
  per-request :class:`~obs.trace.TraceContext` minted at submit,
  propagated across scheduler transitions, prefill/decode legs, KV
  transfers, failover re-admissions, and the process-fleet store wire;
  inert unless ``TPUNN_TRACE`` is set;
- :mod:`obs.critpath` — waterfall assembly + critical-path attribution
  over Causeway spans: per-trace segment decomposition
  (queued/prefill/transfer/failover/restore/decode/stitch) that
  provably sums to end-to-end latency, plus the fleet rollup per SLO
  bucket (``scripts/obs_trace.py`` renders both);
- :mod:`obs.meter` — Abacus per-tenant resource metering (ISSUE 17):
  analytic FLOPs, refcount-weighted KV block-seconds, wire bytes, and
  lifecycle wall time attributed to (tenant, request) pairs at the
  engine/scheduler/pool/collective choke points; ledgers publish at
  ``meter/<rank>`` for fleet rollup and feed ``scripts/obs_cost.py``'s
  showback report; inert unless ``TPUNN_METER`` is set;
- :mod:`obs.audit` — Lighthouse output-integrity auditing (ISSUE 19):
  rolling sha1 fingerprint chains over emitted token ids, shadow
  replay of a sampled request slice to a second replica, golden
  probes at idle cadence, and quarantine of a confirmed-diverging
  replica through the counted state choke points; divergence pages
  land in the watchtower and ``scripts/obs_audit.py`` renders the
  integrity report; inert unless ``TPUNN_AUDIT`` is set;
- :mod:`obs.xray` — anomaly-triggered device profiling (ISSUE 10):
  bounded, rate-limited ``jax.profiler`` captures (page/interval/
  on-demand triggers), per-op MFU/roofline attribution, compile
  telemetry (fed by :mod:`obs.jitwatch`) for the ``recompile_storm``
  detector; inert unless ``TPUNN_XRAY`` is set.

``scripts/obs_report.py`` renders the JSONL/trace output;
``scripts/obs_doctor.py`` analyzes flight dumps;
``scripts/obs_watch.py`` tails/replays alerts and burn rates;
``scripts/obs_xray.py`` renders capture attribution tables.
"""

from pytorch_distributed_nn_tpu.obs import audit  # noqa: F401
from pytorch_distributed_nn_tpu.obs import critpath  # noqa: F401
from pytorch_distributed_nn_tpu.obs import flight  # noqa: F401
from pytorch_distributed_nn_tpu.obs import jitwatch  # noqa: F401
from pytorch_distributed_nn_tpu.obs import meter  # noqa: F401
from pytorch_distributed_nn_tpu.obs import stats  # noqa: F401
from pytorch_distributed_nn_tpu.obs import trace  # noqa: F401
from pytorch_distributed_nn_tpu.obs import watchtower  # noqa: F401
from pytorch_distributed_nn_tpu.obs import xray  # noqa: F401
from pytorch_distributed_nn_tpu.obs.device_counters import (  # noqa: F401
    DeviceCounters,
)
from pytorch_distributed_nn_tpu.obs.goodput import (  # noqa: F401
    PHASES,
    GoodputMeter,
    StepBreakdown,
    serve_loop_records,
)
from pytorch_distributed_nn_tpu.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    get_registry,
    reset_registry,
)
from pytorch_distributed_nn_tpu.obs.span import (  # noqa: F401
    current_recorder,
    disable_tracing,
    enable_tracing,
    merge_chrome_traces,
    span,
    tracing_enabled,
    write_trace,
)


def __getattr__(name):
    # capacity pulls in serve/, whose engine imports back through
    # inference.generate -> obs; an eager import here would leave
    # generate partially initialized. Resolve it on first attribute
    # access instead (PEP 562), when both packages are settled.
    if name == "capacity":
        import importlib
        return importlib.import_module(
            "pytorch_distributed_nn_tpu.obs.capacity")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
