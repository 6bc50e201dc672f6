"""Continuous-batching serving subsystem (ISSUE 5 + fleet, ISSUE 8).

Layering (each module's docstring carries its own contract):

- :mod:`serve.kv_pool` — paged KV-cache accounting: block allocator +
  per-sequence block tables, reservation-at-admission;
- :mod:`serve.scheduler` — bounded admission queue, strict-FIFO
  anti-starvation policy, deadlines, chaos load-shedding;
- :mod:`serve.engine` — the batched decode loop: per-row cache
  positions over one dense KV cache, mid-batch retirement, greedy
  decode bit-identical to sequential ``inference.generate``;
- :mod:`serve.server` — thread loopback front-end, SIGTERM drain,
  open/closed-loop synthetic clients;
- :mod:`serve.traffic` — Skyline trace-driven load generator: seeded
  diurnal/flash-crowd/heavy-tailed multi-tenant traffic shapes
  (``TPUNN_TRAFFIC`` chaos-style spec grammar), byte-identical JSONL
  traces, replay into a server or fleet; the capacity judge lives in
  :mod:`obs.capacity`;
- :mod:`serve.prefix_cache` — Mosaic prefix-cache residency (ISSUE
  14): content-addressed radix index over retired KV blocks, COW
  tail reuse, leaf-only LRU eviction, one counted ``_account`` choke
  point; the engine's save/restore side lives in :mod:`serve.engine`;
- :mod:`serve.router` — fleet placement policy: score READY replicas
  by KV headroom minus queue pressure plus prefix-cache affinity
  (``PrefixCache.peek``), one counted choke point;
- :mod:`serve.fleet` — replica supervisor: N engines behind one
  admission point, heartbeat failure detection, chaos-tested failover
  with in-flight re-admission, rolling zero-reject weight reload,
  elastic ``scale_to`` with a warm-before-READY join gate;
- :mod:`serve.disagg` — Estuary (ISSUE 15): disaggregated
  prefill/decode pools (``Fleet(prefill=P, decode=D)``), KV block
  streaming between replicas through the
  :func:`ops.collectives.kv_transfer` choke point, two-stage
  stage-aware placement, chaos-tested mid-transfer failover with
  bit-identical stitched output;
- :mod:`serve.autoscale` — Helm: the SLO burn-rate autoscaler closing
  the watchtower → fleet loop (``TPUNN_AUTOSCALE`` spec grammar,
  explainable ``autoscale_decision`` journal, hysteresis/cooldowns,
  Skyline-forecast scale-down floor);
- :mod:`serve.store` — the fleet's coordination substrate:
  ``MemStore`` (in-process, parity-tested against the native wire
  client), ``PrefixStore`` namespacing, append-only ``StoreJournal``,
  ``make_store`` endpoint factory;
- :mod:`serve.procfleet` — the deployment shape (ISSUE 13): replica
  subprocesses (:mod:`serve.fleet_worker`) supervised over the real
  native store, with a crash-recoverable coordinator (adoption, not
  restart; journal continuity across incarnations); Breakwater (ISSUE
  18) adds role-tagged pools (``ProcessFleet(prefill=P, decode=D)``)
  and cross-host enrollment through a ``ProcessFleetProvisioner``;
- :mod:`serve.kv_wire` — Breakwater's fault-tolerant KV handoff wire
  (ISSUE 18): versioned, checksummed ``kvwire/<req>/<seq>`` chunk
  records streamed through the store, every op on a counted retry
  helper (:func:`runtime.failure.store_call`), torn chunks re-pulled
  then degraded to a cold re-prefill — a request never wedges.

CLI: ``scripts/serve.py``, ``scripts/fleet_deploy.py``; measured by
``benchmark/run.py``'s serving cells; docs: ``docs/serving.md``.
"""

from pytorch_distributed_nn_tpu.serve.autoscale import (  # noqa: F401
    ENV_AUTOSCALE,
    AutoscaleConfig,
    Autoscaler,
    Decision,
    FleetAutoscaler,
    SimController,
)
from pytorch_distributed_nn_tpu.serve import autoscale  # noqa: F401
from pytorch_distributed_nn_tpu.serve.decoding import (  # noqa: F401
    DecodeSpec,
    TokenStream,
)
from pytorch_distributed_nn_tpu.serve.disagg import (  # noqa: F401
    DisaggFleet,
)
from pytorch_distributed_nn_tpu.serve.engine import (  # noqa: F401
    ServingEngine,
)
from pytorch_distributed_nn_tpu.serve.fleet import (  # noqa: F401
    Fleet,
    FleetTicket,
    ReplicaHandle,
)
from pytorch_distributed_nn_tpu.serve.kv_pool import KVPool  # noqa: F401
from pytorch_distributed_nn_tpu.nn.lora import (  # noqa: F401
    init_lora_bank,
    merge_lora,
    num_adapters,
)
from pytorch_distributed_nn_tpu.serve.prefix_cache import (  # noqa: F401
    PrefixCache,
    PrefixMatch,
)
from pytorch_distributed_nn_tpu.serve import kv_wire  # noqa: F401
from pytorch_distributed_nn_tpu.serve.procfleet import (  # noqa: F401
    ProcessFleet,
    ProcessFleetProvisioner,
    ProcTicket,
    TemplateProvisioner,
)
from pytorch_distributed_nn_tpu.serve.router import (  # noqa: F401
    DEAD,
    DRAINING,
    READY,
    RELOADING,
    REPLICA_STATES,
    STARTING,
    Router,
)
from pytorch_distributed_nn_tpu.serve.scheduler import (  # noqa: F401
    Request,
    Scheduler,
)
from pytorch_distributed_nn_tpu.serve.store import (  # noqa: F401
    MemStore,
    PrefixStore,
    StoreJournal,
    make_store,
)
from pytorch_distributed_nn_tpu.serve.server import (  # noqa: F401
    InferenceServer,
    arrival_offsets,
    closed_loop_client,
    install_sigterm_drain,
    open_loop_client,
    ragged_prompt_sampler,
)
from pytorch_distributed_nn_tpu.serve.traffic import (  # noqa: F401
    ENV_TRAFFIC,
    TrafficSpec,
    generate_trace,
    load_trace,
    replay_trace,
    trace_to_jsonl,
    write_trace,
)
from pytorch_distributed_nn_tpu.serve import traffic  # noqa: F401
