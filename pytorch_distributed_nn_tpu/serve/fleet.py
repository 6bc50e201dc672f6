"""Replica fleet: supervisor, failover, and rolling weight reload.

One :class:`serve.engine.ServingEngine` is one crash domain — when it
dies, every in-flight stream dies with it. This module turns N engines
into a fleet a request can survive:

- **replicas** — each replica is an engine plus a driver thread (the
  worker), a :class:`runtime.failure.HeartbeatReporter` beating into an
  in-process store, and its own :class:`launch.RestartPolicy` (the
  PR-3 restart governor, reused verbatim: budget window, exponential
  backoff + seeded jitter, free restarts for graceful preemption);
- **admission** — :meth:`Fleet.submit` admits a request exactly once
  fleet-wide: the :class:`serve.router.Router` scores READY replicas by
  KV-block headroom and queue depth (one counted choke point), and the
  chosen replica's own scheduler applies the real backpressure;
- **failover** — every admission is journaled (prompt, budget,
  placement). Replica death is detected two ways: a worker exception
  (chaos ``kill_replica`` raises :class:`runtime.chaos.ReplicaKillError`
  in the driver loop) surfaces on the next :meth:`poll`, and a wedged
  worker (chaos ``hang_replica`` sleeps in the driver loop) stops
  notifying its heartbeat's progress watchdog, so the REAL
  :class:`runtime.failure.FailureDetector` flags the replica stale.
  Either way the fleet marks the replica DEAD (counted state change),
  dumps the flight ring, pages the watchtower (``replica_down``), and
  re-admits each stranded request on a survivor with prompt +
  tokens-emitted-so-far as the new prompt — greedy decode is a pure
  function of the sequence prefix, so the stitched stream is
  bit-identical to an uninterrupted run (golden-tested);
- **rolling reload** — :meth:`Fleet.reload` rolls replicas one at a
  time through the graceful-drain contract: the router stops placing on
  the replica, the worker finishes everything it holds and exits with
  ``failure.GRACEFUL_EXIT_CODE`` (83), the restart policy charges
  nothing (``reason="preempt"``), and a fresh engine rejoins with the
  new params. No request is ever rejected by a reload — draining here
  means "stop feeding", never ``scheduler.drain()``'s queued-reject.

Design contract (lint-enforced by tests/test_quality.py, mirroring the
scheduler's ``_transition``): every replica state change goes through
:meth:`Fleet._set_state`, which bumps
``serve_replica_state_total{state}`` and lands a ``fleet`` event in the
flight ring — replica lifecycle can never drift off the books.

Thread model: client threads call :meth:`submit`; each replica's worker
thread drives only its own engine; one supervisor thread (started by
:meth:`start`) calls :meth:`poll` — exits, staleness, delayed restarts,
ticket finalization — under the fleet lock. Workers never take the
fleet lock, so a wedged replica cannot wedge supervision.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import threading
import time
from typing import Optional

import numpy as np

from pytorch_distributed_nn_tpu.launch import RestartPolicy
from pytorch_distributed_nn_tpu.obs import (
    audit,
    flight,
    meter,
    trace,
    watchtower,
)
from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.runtime import chaos, failure
from pytorch_distributed_nn_tpu.serve.engine import ServingEngine
from pytorch_distributed_nn_tpu.serve.router import (
    DEAD,
    DRAINING,
    QUARANTINED,
    READY,
    RELOADING,
    STARTING,
    Router,
)
from pytorch_distributed_nn_tpu.serve.scheduler import DONE, REJECTED

from pytorch_distributed_nn_tpu.serve.store import MemStore, PrefixStore

log = logging.getLogger(__name__)

_ids = itertools.count()

# Back-compat alias: the in-process store grew full StoreClient surface
# parity and moved to serve/store.py (tests/test_store_parity.py pins
# it to the real transport op-for-op).
_MemStore = MemStore


class FleetTicket:
    """The client's handle on one fleet-admitted request. Survives
    failover: the underlying per-replica ``Request`` may be replaced,
    ``done``/``tokens`` here are the logical request's."""

    def __init__(self, request_id: str, prompt: np.ndarray,
                 max_new_tokens: int,
                 deadline_s: Optional[float],
                 tenant: str = "default") -> None:
        self.request_id = request_id
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.deadline_s = deadline_s
        # Abacus (obs/meter.py): the billing identity every leg of this
        # logical request carries — a disagg prefill leg and its decode
        # leg, or a failover re-admission, all bill the same tenant
        self.tenant = str(tenant)
        self.t_submit = time.monotonic()
        self.t_first_token = 0.0
        self.t_done = 0.0
        # tokens emitted by dead replicas, re-fed as prompt suffix on
        # re-admission; final tokens = prefix + surviving life's output
        self.prefix: list[int] = []
        self.failovers: list[dict] = []
        self.status = "pending"  # pending | done | rejected | failed
        self.reject_reason = ""
        self.tokens: Optional[np.ndarray] = None
        self.done = threading.Event()
        self._attempt: Optional[tuple[int, object]] = None
        # disaggregated fleets (serve/disagg.py): which leg the current
        # attempt runs — "" (unified), "prefill", or "decode"
        self.stage = ""
        # Causeway (obs/trace.py): the logical request's TraceContext,
        # re-linked (leg+1, parent=previous root span) on every
        # resubmission; None when unarmed or unsampled
        self.trace = None
        # Prism (serve/decoding.py): the request's DecodeSpec (None =
        # greedy, byte-identity path) — every leg (failover, shadow,
        # referee, disagg decode) carries the same spec so seeded
        # sampling reproduces deterministically across legs
        self.decode = None
        self.n_best = None  # ranked [{branch, tokens, logprob}] (best-of-n)

    @property
    def ok(self) -> bool:
        return self.status == "done"

    @property
    def ttft_s(self) -> float:
        return (self.t_first_token - self.t_submit
                if self.t_first_token else -1.0)

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.done.wait(timeout)

    def result(self, timeout: Optional[float] = None):
        """Block for the tokens; None on timeout or a non-DONE end."""
        if not self.done.wait(timeout):
            return None
        return self.tokens if self.ok else None


class _ReplicaWorker:
    """One replica's driver thread: pump the engine, heartbeat
    progress, honor stop/preempt, and run the chaos replica drill."""

    def __init__(self, index: int, engine: ServingEngine,
                 reporter: failure.HeartbeatReporter,
                 idle_wait_s: float) -> None:
        self.index = index
        self.engine = engine
        self.reporter = reporter
        self.idle_wait_s = idle_wait_s
        self.started_at = time.monotonic()
        self.exit_reason: Optional[str] = None  # ok | preempt | crash
        self.exit_code: Optional[int] = None
        self.error: Optional[BaseException] = None
        # set on the first progress beat from inside the loop: the
        # join gate's proof the driver thread is actually pumping (the
        # reporter's constructor beat is synchronous in the spawning
        # thread and proves nothing about this one)
        self.progressed = threading.Event()
        self._stop = threading.Event()
        self._preempt = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"fleet-r{index}", daemon=True)

    def start(self) -> None:
        self.started_at = time.monotonic()
        self._thread.start()

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def request_stop(self) -> None:
        """Hard stop (death declared / fleet shutdown): the loop exits
        before its next engine touch — a thread waking from an injected
        hang must never step an engine its successor replaced."""
        self._stop.set()

    def request_preempt(self) -> None:
        """Graceful-drain notice (rolling reload): finish everything
        the engine holds, then exit ``GRACEFUL_EXIT_CODE`` — the
        thread-world analog of the PR-3 SIGTERM/exit-83 contract."""
        self._preempt.set()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    def _run(self) -> None:
        code, reason = 0, "ok"
        try:
            while not self._stop.is_set():
                # chaos kill/hang drill, outside the engine's lint-
                # guarded hot loop; may raise (kill) or block (hang)
                chaos.on_replica_round(self.index,
                                       self.engine.scheduler.round + 1)
                if self._stop.is_set():
                    break  # declared dead while hung: hands off
                self.reporter.notify_progress()
                if not self.progressed.is_set():
                    self.progressed.set()
                if self._preempt.is_set() and not self.engine.has_work:
                    code, reason = failure.GRACEFUL_EXIT_CODE, "preempt"
                    break
                if self.engine.has_work:
                    self.engine.step()
                else:
                    time.sleep(self.idle_wait_s)
        except BaseException as e:  # noqa: BLE001 — any death is a crash
            code, reason = chaos.CRASH_EXIT_CODE, "crash"
            self.error = e
            log.warning("fleet replica %d crashed: %r", self.index, e)
        self.exit_code, self.exit_reason = code, reason


@dataclasses.dataclass
class ReplicaHandle:
    """The fleet's book entry for one replica slot. ``state`` is
    written ONLY by :meth:`Fleet._set_state` (lint-enforced)."""

    index: int
    name: str
    policy: RestartPolicy
    engine: Optional[ServingEngine] = None
    worker: Optional[_ReplicaWorker] = None
    reporter: Optional[failure.HeartbeatReporter] = None
    state: str = ""
    incarnations: int = 0
    restart_at: Optional[float] = None
    stop_reason: str = ""
    # join gate (live fleets): a replica entering mid-traffic stays
    # STARTING — invisible to the router — until its warmup jits are
    # compiled AND its worker has beaten progress from inside the loop
    warm_done: bool = True
    # scale-down: draining toward removal; reaped by poll() once empty
    retiring: bool = False
    # pool class (serve/disagg.py): "unified" | "prefill" | "decode";
    # the router's stage= filter keys on this
    role: str = "unified"


class Fleet:
    """N serving replicas behind one admission point."""

    def __new__(cls, *args, **kwargs):
        # ``Fleet(prefill=P, decode=D)`` is the disaggregated
        # constructor: swap in the subclass (serve/disagg.py) so every
        # call site that builds a Fleet today opts into split pools
        # with two kwargs instead of a new import.
        if cls is Fleet and ("prefill" in kwargs
                             or "decode" in kwargs):
            from pytorch_distributed_nn_tpu.serve.disagg import (
                DisaggFleet,
            )
            return super().__new__(DisaggFleet)
        return super().__new__(cls)

    def __init__(self, model, params, *, replicas: int = 2,
                 max_slots: int = 4, max_seq_len: int = 256,
                 block_size: int = 16, max_queue: int = 64,
                 max_prefills_per_round: int = 2,
                 eos_token: Optional[int] = None, metrics=None,
                 max_restarts: int = 3,
                 restart_window_s: Optional[float] = None,
                 backoff_base_s: float = 0.05,
                 backoff_max_s: float = 2.0,
                 heartbeat_interval_s: float = 0.1,
                 heartbeat_timeout_s: float = 10.0,
                 progress_window_s: Optional[float] = None,
                 idle_wait_s: float = 0.002,
                 poll_interval_s: float = 0.01,
                 store=None, namespace: str = "") -> None:
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.model = model
        self.params = params
        self.metrics = metrics
        self.eos_token = eos_token
        self._engine_kw = dict(
            max_slots=max_slots, max_seq_len=max_seq_len,
            block_size=block_size, max_queue=max_queue,
            max_prefills_per_round=max_prefills_per_round)
        self._hb_interval = heartbeat_interval_s
        self._hb_timeout = heartbeat_timeout_s
        self._policy_kw = dict(
            max_restarts=max_restarts, window_s=restart_window_s,
            backoff_base_s=backoff_base_s, backoff_max_s=backoff_max_s)
        self._progress_window = (progress_window_s
                                 if progress_window_s is not None
                                 else max(heartbeat_timeout_s / 2,
                                          2 * heartbeat_interval_s))
        self._idle_wait = idle_wait_s
        self._poll_interval = poll_interval_s
        self.router = Router()
        # Heartbeat transport: in-process by default; pass ``store=``
        # (e.g. a runtime.native.StoreClient) to beat through the real
        # wire instead — the protocol is identical either way (the
        # store-parity suite guarantees it). ``namespace`` scopes every
        # key under ``<namespace>/`` so one physical store can host
        # many fleets (and the process-backed fleet's coordinator
        # state) without collisions.
        base_store = store if store is not None else MemStore()
        self._store = (PrefixStore(base_store, namespace)
                       if namespace else base_store)
        self._detector = failure.FailureDetector(
            self._store, ranks=list(range(replicas)), incarnation=0,
            timeout_s=heartbeat_timeout_s)
        self._lock = threading.RLock()
        self._journal: dict[str, FleetTicket] = {}
        self.completed: list[dict] = []
        self.failovers = 0
        # Lighthouse (obs/audit.py) shadow-replay bookkeeping: pending
        # comparisons keyed by the primary's request id. Empty forever
        # on an unarmed process (shadow_sampled is always False).
        self._shadows: dict[str, dict] = {}
        self._referees: dict[str, tuple[int, object]] = {}
        self._probes: list[tuple[int, object]] = []
        self._probe_n = 0
        self._last_probe_t = time.monotonic()
        reg = get_registry()
        self._c_replica_state = reg.counter(
            "serve_replica_state_total", "replica state transitions",
            labels=("state",))
        self._replicas: list[ReplicaHandle] = []
        for i in range(replicas):
            h = self._new_handle(i)
            self._replicas.append(h)
            self._set_state(h, STARTING, reason="init")
            self._spawn(h, params)
            self._set_state(h, READY, reason="up")
        self._target_replicas = replicas
        self._next_index = replicas
        self._started = False
        self._sup_stop = threading.Event()
        self._sup_thread: Optional[threading.Thread] = None

    # -- the single replica-state choke point ------------------------------

    def _set_state(self, h: ReplicaHandle, state: str,
                   reason: str = "") -> None:
        """EVERY replica state change funnels through here (lint-
        enforced): the ``serve_replica_state_total{state}`` counter and
        the flight ring can't drift from the fleet's actual shape."""
        h.state = state
        self._c_replica_state.inc(state=state)
        flight.record("fleet", f"state:{state}",
                      note=f"{h.name} {reason}".strip())
        if self.metrics is not None:
            self.metrics.emit("fleet_state", replica=h.index,
                              state=state, reason=reason)

    # -- replica lifecycle -------------------------------------------------

    def _new_handle(self, index: int) -> ReplicaHandle:
        return ReplicaHandle(
            index=index, name=f"r{index}",
            policy=RestartPolicy(seed=index, **self._policy_kw))

    def _rebuild_detector(self) -> None:
        """Point the failure detector at the current membership.
        Replica indexes are never reused (``_next_index`` is monotonic)
        so a retired slot's stale heartbeat keys can't alias a newer
        replica's."""
        self._detector = failure.FailureDetector(
            self._store, ranks=[h.index for h in self._replicas],
            incarnation=0, timeout_s=self._hb_timeout)

    def _spawn(self, h: ReplicaHandle, params) -> None:
        """Fresh engine + heartbeat + worker for one replica slot (first
        start, post-crash restart, or post-reload rejoin)."""
        h.engine = ServingEngine(
            self.model, params, eos_token=self.eos_token,
            metrics=self.metrics, tag=h.name, **self._engine_kw)
        # chaos flip@replica=K keys on the fleet index (obs/audit.py
        # silent-corruption drill); standalone engines keep 0
        h.engine.replica_index = h.index
        h.reporter = failure.HeartbeatReporter(
            self._store, rank=h.index, incarnation=0,
            interval_s=self._hb_interval,
            progress_window_s=self._progress_window)
        h.worker = _ReplicaWorker(h.index, h.engine, h.reporter,
                                  self._idle_wait)
        h.incarnations += 1
        h.restart_at = None
        if getattr(self, "_started", False):
            h.worker.start()

    def _admit_joining(self, h: ReplicaHandle, *,
                       reason: str) -> None:
        """Bring a freshly spawned replica into the routable set. On a
        stopped fleet that is immediate (``run_until_idle`` drives the
        engine directly; there is no cold compile to misread as a
        hang). On a live fleet the replica stays STARTING — the router
        never places on it — until the join gate opens: its warmup jits
        compiled (a background warm thread; the jit cache is keyed on
        the model, so an already-warm fleet passes in microseconds) AND
        its worker has beaten progress from inside the driver loop.
        :meth:`_promote_joining` flips it READY on a later poll."""
        if not self._started:
            h.warm_done = True
            self._set_state(h, READY, reason=reason)
            return
        h.warm_done = False
        engine = h.engine

        def _warm() -> None:
            try:
                self.warmup(engine=engine)
            except Exception:
                # open the gate anyway: a genuinely broken replica
                # surfaces through the normal crash/staleness paths
                log.exception("fleet: warmup for %s failed", h.name)
            h.warm_done = True

        threading.Thread(target=_warm, name=f"fleet-warm-{h.name}",
                         daemon=True).start()

    def _promote_joining(self) -> None:
        """Open the join gate: STARTING replicas whose warmup finished
        and whose worker proved liveness become READY (routable)."""
        for h in self._replicas:
            if (h.state == STARTING and h.warm_done
                    and not h.retiring and h.worker is not None
                    and h.worker.progressed.is_set()):
                self._set_state(h, READY, reason="join:warm+beat")

    def warmup(self, prompt_lens=(8,), *, engine=None) -> None:
        """Compile a replica's serve programs
        (:meth:`ServingEngine.warmup`) before any worker thread runs
        them. Without this, the first decode on a cold process stalls a
        worker for the whole XLA compile — long enough to starve its
        progress watchdog and read as a hang to the failure detector (a
        false replica_down on a healthy fleet). The jit cache is keyed
        on the model so every replica shares the result."""
        eng = engine if engine is not None else self._replicas[0].engine
        eng.warmup(prompt_lens)

    def start(self, *, warmup_prompt_lens=(8,)) -> "Fleet":
        """Start every replica's worker plus the supervisor thread.
        Compiles the serve jits first (see :meth:`warmup`) so a cold
        process cannot misread compilation as a hung replica; pass
        ``warmup_prompt_lens=()`` to skip."""
        if self._started:
            return self
        if warmup_prompt_lens:
            self.warmup(warmup_prompt_lens)
        self._started = True
        for h in self._replicas:
            if h.worker is not None and not h.worker.alive \
                    and h.worker.exit_reason is None:
                h.worker.start()
        self._sup_thread = threading.Thread(
            target=self._supervise, name="fleet-supervisor", daemon=True)
        self._sup_thread.start()
        return self

    def _supervise(self) -> None:
        while not self._sup_stop.wait(self._poll_interval):
            try:
                self.poll()
            except Exception:  # supervision must outlive any one fault
                log.exception("fleet poll failed")

    def stop(self) -> None:
        """Shut the fleet down: stop workers, finish in-flight work
        synchronously, reject whatever is still queued (``draining``),
        release heartbeats, finalize every ticket."""
        if self._sup_thread is not None:
            self._sup_stop.set()
            self._sup_thread.join(timeout=5.0)
            self._sup_thread = None
        for h in self._replicas:
            if h.worker is not None and h.worker.alive:
                h.worker.request_stop()
                h.worker.join(timeout=5.0)
            if h.state not in (DEAD,):
                self._set_state(h, DRAINING, reason="stop")
                if h.engine is not None and not (
                        h.worker is not None and h.worker.alive):
                    h.engine.drain()
            if h.reporter is not None:
                h.reporter.stop()
        self._started = False
        self.poll()

    def __enter__(self) -> "Fleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client surface ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               deadline_s: Optional[float] = None,
               request_id: Optional[str] = None,
               tenant: str = "default",
               decode=None) -> FleetTicket:
        """Admit once, place once (router-scored), journal for
        failover. Always returns a ticket; a rejected one is already
        terminal. ``decode`` (a :class:`serve.decoding.DecodeSpec`)
        rides the ticket so every leg — failover, shadow, referee,
        disagg decode — reproduces the same seeded stream."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        ticket = FleetTicket(
            request_id or f"freq-{next(_ids)}", prompt,
            max_new_tokens, deadline_s, tenant=tenant)
        ticket.decode = decode
        # Causeway mint point: the context outlives every per-replica
        # Request this ticket will spawn
        ticket.trace = trace.on_submit(ticket.request_id)
        with self._lock:
            self._journal[ticket.request_id] = ticket
            placed = self._place(ticket, prompt, int(max_new_tokens),
                                 resubmit=False)
            # Lighthouse shadow replay: a deterministic request-id-hash
            # sample runs AGAIN on a second replica; the fingerprint
            # compare happens in _audit_poll once both legs finish.
            # Inert one-call no-op unless TPUNN_AUDIT armed it.
            if placed is not None \
                    and audit.shadow_sampled(ticket.request_id):
                self._submit_shadow(ticket, prompt,
                                    int(max_new_tokens),
                                    primary=placed)
        return ticket

    def generate(self, prompt, max_new_tokens: int,
                 timeout: Optional[float] = None):
        """Blocking convenience: submit + wait, tokens or None."""
        ticket = self.submit(prompt, max_new_tokens)
        if not self._started:
            self.run_until_idle()
        return ticket.result(timeout)

    def run_until_idle(self) -> None:
        """Synchronous drive (fleet not started): round-robin every
        live engine until all queues and batches are empty, finalizing
        tickets as they finish. Deterministic — tests use this."""
        while True:
            busy = False
            for h in self._replicas:
                if h.state in (READY, DRAINING, RELOADING) \
                        and h.engine is not None and h.engine.has_work:
                    h.engine.step()
                    busy = True
            self.poll()
            if busy:
                continue
            # poll() itself can create work after an idle sweep — a
            # failover re-admission or a disagg prefill->decode handoff
            # lands new queue entries — so only an idle sweep FOLLOWED
            # by an idle poll terminates
            if any(h.state in (READY, DRAINING, RELOADING)
                   and h.engine is not None and h.engine.has_work
                   for h in self._replicas):
                continue
            return

    # -- placement ---------------------------------------------------------

    def _place(self, ticket: FleetTicket, prompt: np.ndarray,
               max_new: int, *, resubmit: bool) -> Optional[int]:
        """One admission attempt through the router (caller holds the
        fleet lock). Terminalizes the ticket when no replica is ready
        or the chosen replica rejects. Returns the replica index that
        accepted the request, None otherwise."""
        branches = (ticket.decode.branches
                    if ticket.decode is not None else 1)
        h = self.router.place(self._replicas,
                              len(prompt) + max_new, prompt=prompt,
                              branches=branches)
        if h is None:
            self._finalize_rejected(ticket, "no_replica")
            return None
        req = h.engine.submit(
            prompt, max_new, deadline_s=ticket.deadline_s,
            request_id=ticket.request_id, resubmit=resubmit,
            tenant=ticket.tenant,
            # Prism: the leg samples the SAME (seed, branch, step)
            # lanes, resumed at the step the prefix already covers
            decode=ticket.decode, decode_step0=len(ticket.prefix),
            trace_ctx=ticket.trace, t_origin=ticket.t_submit,
            t_first_origin=ticket.t_first_token,
            # Lighthouse: the leg resumes the chain over the tokens
            # earlier lives already emitted ("" unarmed — key-absent)
            fp_seed=audit.seed_of(ticket.prefix)
            if audit.enabled() else "")
        ticket._attempt = (h.index, req)
        if req.done.is_set() and req.state == REJECTED:
            self._finalize_rejected(ticket, req.reject_reason)
            return None
        return h.index

    # -- supervision -------------------------------------------------------

    def poll(self) -> None:
        """One supervision pass: crashed workers, stale heartbeats,
        due restarts, finished tickets. Thread-safe; the supervisor
        thread calls it continuously once :meth:`start` has run."""
        with self._lock:
            self._check_exits()
            self._check_stale()
            self._restart_due()
            self._promote_joining()
            self._reap_retiring()
            self._finalize_tickets()
            # Lighthouse: golden probes at idle cadence + pending
            # shadow/referee fingerprint comparisons. Both are inert
            # one-call no-ops unless TPUNN_AUDIT armed the process.
            self._maybe_probe()
            self._audit_poll()

    def _check_exits(self) -> None:
        for h in self._replicas:
            if (h.state != DEAD and h.worker is not None
                    and h.worker.exit_reason == "crash"):
                err = h.worker.error
                self._fail_replica(
                    h, kind="crash",
                    reason=f"crash:{type(err).__name__}"
                    if err is not None else "crash")

    def _check_stale(self) -> None:
        alive = {h.index for h in self._replicas
                 if h.state != DEAD and h.worker is not None
                 and h.worker.alive and h.worker.exit_reason is None}
        if not alive:
            return
        by_index = {h.index: h for h in self._replicas}
        for idx in self._detector.stale_ranks(alive=alive):
            self._fail_replica(by_index[idx], kind="hang",
                               reason="hang:heartbeat_stale")

    def _fail_replica(self, h: ReplicaHandle, *, kind: str,
                      reason: str) -> None:
        """The failover core: declare the replica dead (counted), dump
        the ring, page the watchtower, re-admit every stranded request
        on a survivor, and schedule the restart the policy allows."""
        stranded = self._stranded_of(h)
        ids = [t.request_id for t, _ in stranded]
        self._set_state(h, DEAD, reason=reason)
        if h.worker is not None:
            h.worker.request_stop()
        if h.reporter is not None:
            h.reporter.stop()
        flight.record("fleet", "replica_down",
                      note=f"{h.name} reason={reason} "
                           f"stranded={','.join(ids)}")
        # a dead replica is a post-mortem: the ring must reach disk now
        flight.dump_now(f"replica_down:{h.name}", force=True)
        watchtower.on_replica_down(h.index, reason, ids)
        if self.metrics is not None:
            self.metrics.emit("fleet_replica_down", replica=h.index,
                              reason=reason, stranded=ids)
        log.warning("fleet: replica %s down (%s), re-admitting %d "
                    "stranded request(s)", h.name, reason, len(ids))
        # Lighthouse legs on the dead replica can never finish — drop
        # their pending comparisons (shadows are never journaled, so
        # the failover machinery above does not touch them)
        self._shadows = {rid: p for rid, p in self._shadows.items()
                         if p["sidx"] != h.index}
        self._referees = {rid: r for rid, r in self._referees.items()
                          if r[0] != h.index}
        self._probes = [(i, r) for i, r in self._probes
                        if i != h.index]
        t_detect = time.monotonic()
        for ticket, emitted in stranded:
            self._readmit(ticket, emitted, from_replica=h.index,
                          t_detect=t_detect, reason=reason)
        worker = h.worker
        duration = (time.monotonic() - worker.started_at
                    if worker is not None else 0.0)
        code = (worker.exit_code if worker is not None
                and worker.exit_code is not None
                else chaos.CRASH_EXIT_CODE)
        decision = h.policy.on_exit(
            reason=kind, code=code, duration_s=duration,
            beat_seen=True)
        if decision.action == "restart":
            h.restart_at = time.monotonic() + decision.delay_s
        else:
            h.restart_at = None
            h.stop_reason = decision.why
            log.warning("fleet: replica %s stays down: %s", h.name,
                        decision.why)

    def _stranded_of(self, h: ReplicaHandle) -> list[tuple]:
        """(ticket, tokens-emitted-so-far) for every journaled request
        whose current life sits on this replica and isn't terminal —
        running requests recover their slot's emitted tokens, queued
        ones restart from the bare prompt."""
        out = []
        for ticket in self._journal.values():
            if ticket.done.is_set() or ticket._attempt is None:
                continue
            idx, req = ticket._attempt
            if idx != h.index or req.done.is_set():
                continue  # terminal lives finalize normally
            emitted: list[int] = []
            branched = (ticket.decode is not None
                        and ticket.decode.branches > 1)
            if h.engine is not None and not branched:
                # best-of-n requests restart from the bare prompt: one
                # branch's tail is not "the" stream, and deterministic
                # seeding re-derives every branch identically anyway
                for slot in h.engine._slots:
                    if slot is not None and slot.req is req:
                        emitted = [int(t) for t in slot.tokens]
                        break
            if emitted and ticket.t_first_token == 0.0:
                ticket.t_first_token = req.t_first_token
            out.append((ticket, emitted))
        return out

    def _readmit(self, ticket: FleetTicket, emitted: list[int], *,
                 from_replica: int, t_detect: float,
                 reason: str) -> None:
        """Re-admit one stranded request on a survivor: prompt +
        emitted-so-far becomes the new prompt (greedy re-decode is
        output-invariant), the remaining budget the new max_new."""
        ticket.prefix.extend(emitted)
        remaining = ticket.max_new_tokens - len(ticket.prefix)
        if remaining <= 0:  # stream was already complete; just stitch
            self._finalize_done(ticket, from_replica)
            return
        new_prompt = ticket.prompt
        if ticket.prefix:
            new_prompt = np.concatenate(
                [ticket.prompt,
                 np.asarray(ticket.prefix, np.int32)])
        self.failovers += 1
        # Causeway: the re-admitted leg gets a linked child context
        # (same trace_id, leg+1, parent = the dead leg's root span)
        nxt = trace.on_resubmit(ticket.trace)
        if nxt is not None:
            ticket.trace = nxt
        placed = self._place(ticket, new_prompt, remaining,
                             resubmit=True)
        readmit_s = time.monotonic() - t_detect
        trace.on_segment(ticket.trace, "failover", t_detect,
                         time.monotonic(),
                         request_id=ticket.request_id,
                         from_replica=from_replica, reason=reason)
        to_replica = placed if placed is not None else -1
        fo = dict(from_replica=from_replica, to_replica=to_replica,
                  reason=reason, readmit_s=round(readmit_s, 6),
                  prefix_tokens=len(ticket.prefix))
        ticket.failovers.append(fo)
        flight.record("fleet", "readmit",
                      note=f"{ticket.request_id} r{from_replica}->"
                           f"r{to_replica} prefix={len(ticket.prefix)}")
        if self.metrics is not None:
            self.metrics.emit("fleet_failover",
                              request_id=ticket.request_id, **fo)

    # -- Lighthouse output-integrity auditing (obs/audit.py) ---------------

    def _submit_shadow(self, ticket: FleetTicket, prompt: np.ndarray,
                       max_new: int, *, primary: int) -> None:
        """Duplicate one sampled request onto a second READY replica
        (caller holds the fleet lock). The shadow leg rides the
        reserved audit tenant — never billed, never TTFT-observed
        (``t_first_origin`` pre-set) — and is not journaled: it can
        never fail over, only finish or die with its replica."""
        h = self.router.place_shadow(
            self._replicas, len(prompt) + max_new,
            exclude=primary, prompt=prompt)
        if h is None:
            return  # single-replica fleet: nothing to compare against
        try:
            req = h.engine.submit(
                prompt, max_new,
                request_id=ticket.request_id + "#shadow",
                tenant=audit.SHADOW_TENANT,
                # Prism: the shadow leg samples the same seeded lanes,
                # so sampled streams are comparable fingerprints too
                decode=ticket.decode,
                t_first_origin=ticket.t_submit)
        except ValueError:
            return
        if req.done.is_set() and req.state == REJECTED:
            return
        self._shadows[ticket.request_id] = dict(
            ticket=ticket, sreq=req, sidx=h.index)

    def _maybe_probe(self) -> None:
        """Push the canned golden probe through every READY replica at
        ``probe_every_s`` cadence, only when the fleet is idle — the
        probe audits capacity that real traffic (and the shadow
        sample) is not reaching; it must never displace a customer."""
        every = audit.probe_interval()
        if not every:
            return
        now = time.monotonic()
        if now - self._last_probe_t < every:
            return
        if any(h.state == READY and h.engine is not None
               and h.engine.has_work for h in self._replicas):
            return  # not idle; try again next poll
        self._last_probe_t = now
        self._probe_n += 1
        for h in self._replicas:
            if h.state != READY or h.engine is None:
                continue
            try:
                req = h.engine.submit(
                    np.asarray(audit.PROBE_PROMPT, np.int32),
                    audit.PROBE_BUDGET,
                    request_id=f"auditprobe-{self._probe_n}-r{h.index}",
                    tenant=audit.SHADOW_TENANT,
                    t_first_origin=now)
            except ValueError:
                continue
            self._probes.append((h.index, req))

    def _audit_poll(self) -> None:
        """Settle pending audit comparisons (caller holds the fleet
        lock): finished probes against the golden, finished shadow
        pairs against each other — a mismatch launches a third
        *referee* leg and the majority names the suspect."""
        if not audit.enabled():
            return
        for idx, req in list(self._probes):
            if not req.done.is_set():
                continue
            try:
                self._probes.remove((idx, req))
            except ValueError:
                continue  # purged by a quarantine earlier this sweep
            if req.state != DONE or req.tokens is None:
                continue  # shed probe: no integrity evidence either way
            fp = audit.chain("", req.tokens)
            if not audit.on_probe_result("p0", f"r{idx}", fp):
                self._confirm_divergence(
                    "probe", request_id=req.request_id,
                    pair=(f"r{idx}",), suspect_idx=idx,
                    note="golden mismatch")
        for rid, pend in list(self._shadows.items()):
            if rid not in self._shadows:
                continue  # purged by a quarantine earlier this sweep
            ticket, sreq = pend["ticket"], pend["sreq"]
            sidx = pend["sidx"]
            if not (sreq.done.is_set() and ticket.done.is_set()):
                continue
            if ticket.status != "done" or sreq.state != DONE \
                    or sreq.tokens is None or ticket.tokens is None:
                self._shadows.pop(rid, None)  # a shed leg proves nothing
                self._referees.pop(rid, None)
                continue
            pidx = (ticket._attempt[0] if ticket._attempt is not None
                    else -1)
            pfp = audit.chain("", ticket.tokens)
            sfp = audit.chain("", sreq.tokens)
            if pfp == sfp:
                self._shadows.pop(rid, None)
                continue
            ref = self._referees.get(rid)
            if ref is None:
                # two-way disagreement: a third leg on a replica
                # outside the pair breaks the tie by majority
                h = self.router.place_shadow(
                    self._replicas,
                    len(ticket.prompt) + ticket.max_new_tokens,
                    exclude=(pidx, sidx), prompt=ticket.prompt)
                rreq = None
                if h is not None:
                    try:
                        rreq = h.engine.submit(
                            ticket.prompt, ticket.max_new_tokens,
                            request_id=rid + "#referee",
                            tenant=audit.SHADOW_TENANT,
                            decode=ticket.decode,
                            t_first_origin=time.monotonic())
                    except ValueError:
                        rreq = None
                if rreq is None:
                    # no third replica: blame the primary
                    # (conservative — the customer-facing leg is the
                    # one whose output we cannot vouch for)
                    self._settle_shadow(rid, ticket, sreq,
                                        pidx=pidx, sidx=sidx,
                                        suspect_idx=pidx)
                    continue
                self._referees[rid] = (h.index, rreq)
                continue
            _ridx, rreq = ref
            if not rreq.done.is_set():
                continue
            rfp = (audit.chain("", rreq.tokens)
                   if rreq.state == DONE and rreq.tokens is not None
                   else "")
            # majority: the leg the referee agrees with is honest;
            # three-way disagreement blames the primary (conservative)
            suspect_idx = sidx if rfp == pfp else pidx
            self._settle_shadow(rid, ticket, sreq, pidx=pidx,
                                sidx=sidx, suspect_idx=suspect_idx)

    def _settle_shadow(self, rid: str, ticket: FleetTicket, sreq, *,
                       pidx: int, sidx: int,
                       suspect_idx: int) -> None:
        """A confirmed shadow divergence: page + quarantine, and when
        the PRIMARY leg is the suspect, repair the client-facing
        tokens with the majority (shadow) output — the customer gets
        the honest stream even though the diverging replica already
        'finished' the request."""
        self._shadows.pop(rid, None)
        self._referees.pop(rid, None)
        repaired = False
        if suspect_idx == pidx and sreq.tokens is not None:
            ticket.tokens = np.asarray(sreq.tokens, np.int32)
            repaired = True
        self._confirm_divergence(
            "shadow", request_id=rid,
            pair=(f"r{pidx}", f"r{sidx}"), suspect_idx=suspect_idx,
            note="repaired" if repaired else "")

    def _confirm_divergence(self, kind: str, *, request_id: str,
                            pair, suspect_idx: int,
                            note: str = "") -> None:
        """Record + page one confirmed divergence, then quarantine the
        suspect (policy-gated). The watchtower page auto-dumps the
        flight ring and triggers an Xray capture — evidence first,
        isolation second."""
        audit.on_divergence(kind, request_id=request_id, pair=pair,
                            suspect=f"r{suspect_idx}", note=note)
        watchtower.on_output_divergence(
            kind, request_id=request_id, pair=pair,
            suspect=f"r{suspect_idx}")
        if not audit.quarantine_enabled():
            return
        h = next((x for x in self._replicas
                  if x.index == suspect_idx), None)
        if h is not None:
            self._quarantine_replica(
                h, reason=f"{kind}_divergence:{request_id}")

    def _quarantine_replica(self, h: ReplicaHandle, *,
                            reason: str) -> None:
        """Isolate a confirmed-diverging replica: QUARANTINED through
        the counted choke point (router excludes it exactly like
        DEAD), worker stopped, in-flight requests re-admitted on
        survivors through the existing failover machinery — and NO
        restart, ever: the process passes every liveness check, which
        is exactly why it must not serve."""
        if h.state in (DEAD, QUARANTINED):
            return
        stranded = self._stranded_of(h)
        ids = [t.request_id for t, _ in stranded]
        self._set_state(h, QUARANTINED, reason=reason)
        if h.worker is not None:
            h.worker.request_stop()
        if h.reporter is not None:
            h.reporter.stop()
        h.restart_at = None
        h.stop_reason = f"quarantined:{reason}"
        audit.on_quarantine(h.name, reason)
        flight.record("fleet", "quarantine",
                      note=f"{h.name} reason={reason} "
                           f"stranded={','.join(ids)}")
        flight.dump_now(f"quarantine:{h.name}", force=True)
        if self.metrics is not None:
            self.metrics.emit("fleet_quarantine", replica=h.index,
                              reason=reason, stranded=ids)
        log.warning("fleet: replica %s QUARANTINED (%s), re-admitting "
                    "%d in-flight request(s)", h.name, reason,
                    len(ids))
        # audit legs queued on the quarantined replica will never
        # finish (the worker is stopped): drop their comparisons
        self._shadows = {rid: p for rid, p in self._shadows.items()
                         if p["sidx"] != h.index}
        self._referees = {rid: r for rid, r in self._referees.items()
                          if r[0] != h.index}
        self._probes = [(i, r) for i, r in self._probes
                        if i != h.index]
        t_detect = time.monotonic()
        for ticket, emitted in stranded:
            self._readmit(ticket, emitted, from_replica=h.index,
                          t_detect=t_detect,
                          reason=f"quarantine:{reason}")

    def _restart_due(self) -> None:
        now = time.monotonic()
        for h in self._replicas:
            if (h.state == DEAD and not h.retiring
                    and h.restart_at is not None
                    and now >= h.restart_at):
                self._set_state(h, STARTING,
                                reason=f"restart #{h.incarnations}")
                self._spawn(h, self.params)
                self._admit_joining(h, reason="up")

    def _finalize_tickets(self) -> None:
        for ticket in list(self._journal.values()):
            if ticket.done.is_set() or ticket._attempt is None:
                continue
            idx, req = ticket._attempt
            if not req.done.is_set():
                continue
            if req.state == DONE:
                if ticket.t_first_token == 0.0:
                    ticket.t_first_token = req.t_first_token
                self._finalize_done(ticket, idx)
            else:
                self._finalize_rejected(
                    ticket, req.reject_reason or req.state,
                    failed=(req.state == "failed"))

    def _finalize_done(self, ticket: FleetTicket,
                       replica: int) -> None:
        tail = []
        if ticket._attempt is not None:
            _, req = ticket._attempt
            if req.tokens is not None:
                tail = [int(t) for t in req.tokens]
            # Prism best-of-n: the ranked alternates ride the ticket
            # (None for unbranched requests — attribute stays inert)
            ticket.n_best = getattr(req, "n_best", None)
        ticket.tokens = np.asarray(ticket.prefix + tail, np.int32)
        ticket.t_done = time.monotonic()
        ticket.status = "done"
        rec = dict(
            request_id=ticket.request_id,
            prompt_len=len(ticket.prompt),
            new_tokens=len(ticket.tokens),
            ttft_s=round(ticket.ttft_s, 6),
            total_s=round(ticket.t_done - ticket.t_submit, 6),
            replica=f"r{replica}", failovers=ticket.failovers)
        self.completed.append(rec)
        del self._journal[ticket.request_id]
        ticket.done.set()

    def _finalize_rejected(self, ticket: FleetTicket, reason: str,
                           failed: bool = False) -> None:
        ticket.reject_reason = reason
        ticket.t_done = time.monotonic()
        ticket.status = "failed" if failed else "rejected"
        self._journal.pop(ticket.request_id, None)
        ticket.done.set()

    # -- rolling reload ----------------------------------------------------

    def reload(self, params) -> dict:
        """Live weight reload, one replica at a time: exclude from
        placement (RELOADING), graceful-drain the worker (it finishes
        everything it holds, exits ``GRACEFUL_EXIT_CODE``), restart
        with the new params (policy charges nothing: ``preempt``),
        rejoin READY. Under steady load the remaining replicas absorb
        placement the whole time and nothing is ever rejected with
        ``draining`` — this path never calls ``scheduler.drain()``.

        Returns ``{replicas_rolled, skipped_dead}``."""
        rolled, skipped = 0, 0
        self.params = params
        for h in list(self._replicas):
            if h.state == DEAD:
                skipped += 1  # a later restart picks up self.params
                continue
            with self._lock:
                self._set_state(h, RELOADING, reason="reload")
            worker = h.worker
            if worker is not None and worker.alive:
                worker.request_preempt()
                worker.join(timeout=120.0)
                if worker.alive:
                    raise RuntimeError(
                        f"fleet reload: replica {h.name} did not "
                        f"drain in time")
            else:
                # synchronous fleet: drain by stepping in place
                while h.engine is not None and h.engine.has_work:
                    h.engine.step()
                self.poll()
            with self._lock:
                duration = (time.monotonic() - worker.started_at
                            if worker is not None else 0.0)
                h.policy.on_exit(
                    reason="preempt", code=failure.GRACEFUL_EXIT_CODE,
                    duration_s=duration, beat_seen=True)
                if h.reporter is not None:
                    h.reporter.stop()
                self._spawn(h, params)
                self._set_state(h, READY, reason="reloaded")
                rolled += 1
            flight.record("fleet", "reload", note=f"{h.name} rejoined")
        if self.metrics is not None:
            self.metrics.emit("fleet_reload", replicas=rolled,
                              skipped_dead=skipped)
        return dict(replicas_rolled=rolled, skipped_dead=skipped)

    # -- elastic scaling ---------------------------------------------------

    def scale_to(self, n: int, *, reason: str = "") -> dict:
        """Resize the replica set to ``n`` slots — the Helm
        autoscaler's actuator (:mod:`serve.autoscale`), equally usable
        by hand.

        Scale **up** appends fresh slots (monotonic indexes, never
        reused) and admits each through the join gate: on a live fleet
        a joiner stays STARTING — unroutable — until its warmup jits
        compile and its worker beats progress, so a cold compile can
        never read as a hang or swallow a routed request. Scale
        **down** retires the highest-index non-retiring slots through
        the reload-style graceful drain: DRAINING (the router stops
        placing immediately), the worker finishes everything the
        engine holds and exits ``GRACEFUL_EXIT_CODE``, and a later
        :meth:`poll` reaps the empty slot — this path never calls
        ``scheduler.drain()``, so scaling down rejects nothing, ever.

        Retiring slots no longer count toward the fleet's size intent,
        so ``scale_to(2)`` on a 4-replica fleet followed by
        ``scale_to(3)`` before the drains finish adds one fresh slot
        rather than resurrecting a draining one (a drain in flight is
        not cancellable without racing its worker's exit).

        Returns ``{target, added, retiring}``."""
        n = int(n)
        if n < 1:
            raise ValueError(f"scale_to: n must be >= 1, got {n}")
        with self._lock:
            current = [h for h in self._scalable() if not h.retiring]
            delta = n - len(current)
            added, retiring = 0, 0
            if delta > 0:
                for _ in range(delta):
                    h = self._new_handle(self._next_index)
                    self._next_index += 1
                    self._replicas.append(h)
                    self._set_state(h, STARTING, reason="scale_up")
                    self._spawn(h, self.params)
                    self._admit_joining(h, reason="scale_up")
                    added += 1
                self._rebuild_detector()
            elif delta < 0:
                doomed = sorted(current, key=lambda r: -r.index)
                for h in doomed[:-delta]:
                    h.retiring = True
                    h.restart_at = None  # a dead slot stays down
                    if h.state != DEAD:
                        self._set_state(h, DRAINING,
                                        reason="scale_down")
                    if h.worker is not None and h.worker.alive:
                        h.worker.request_preempt()
                    retiring += 1
            self._target_replicas = n
            flight.record(
                "fleet", "scale_to",
                note=f"target={n} added={added} retiring={retiring}"
                     + (f" {reason}" if reason else ""))
            if self.metrics is not None:
                self.metrics.emit("fleet_scale", target=n, added=added,
                                  retiring=retiring, reason=reason)
            # idle retirees on a synchronous fleet reap right here
            self._reap_retiring()
        return dict(target=n, added=added, retiring=retiring)

    def _scalable(self) -> list[ReplicaHandle]:
        """The handles ``scale_to``'s size intent counts against. The
        unified fleet scales every slot; the disaggregated fleet
        (:mod:`serve.disagg`) narrows this to the decode pool — decode
        is the KV/bandwidth-bound class Helm's burn-rate evidence
        actually measures."""
        return self._replicas

    def _reap_retiring(self) -> None:
        """Release retired slots whose drain completed: worker exited
        (gracefully — or, on a synchronous fleet, the engine emptied
        under ``run_until_idle``), policy credited as a preemption,
        heartbeat released, handle dropped from the books. Membership
        changed ⇒ the failure detector is rebuilt."""
        done = []
        for h in self._replicas:
            if not h.retiring:
                continue
            if h.state != DEAD:
                if h.worker is not None and h.worker.alive:
                    continue  # still draining
                if h.engine is not None and h.engine.has_work:
                    continue  # synchronous fleet: still being stepped
            done.append(h)
        if not done:
            return
        for h in done:
            if h.worker is not None and h.state != DEAD:
                h.policy.on_exit(
                    reason="preempt", code=failure.GRACEFUL_EXIT_CODE,
                    duration_s=time.monotonic() - h.worker.started_at,
                    beat_seen=True)
            if h.reporter is not None:
                h.reporter.stop()
            self._replicas.remove(h)
            flight.record("fleet", "retired", note=h.name)
        self._rebuild_detector()

    # -- introspection -----------------------------------------------------

    @property
    def replicas(self) -> list[ReplicaHandle]:
        return list(self._replicas)

    @property
    def live_replicas(self) -> int:
        return sum(1 for h in self._replicas if h.state == READY)

    @property
    def target_replicas(self) -> int:
        """The size intent (last ``scale_to`` target, or the
        constructed size) — what the fleet is converging toward."""
        return self._target_replicas

    def summary(self) -> dict:
        """Fleet-lifetime aggregates (bench + fleet_summary JSONL)."""
        per_replica = []
        for h in self._replicas:
            eng = h.engine.summary() if h.engine is not None else {}
            per_replica.append(dict(
                replica=h.name, state=h.state, role=h.role,
                incarnations=h.incarnations,
                budget_restarts=h.policy.budget_restarts,
                preempt_restarts=h.policy.preempt_restarts,
                stop_reason=h.stop_reason, **eng))
        out = dict(
            replicas=len(self._replicas),
            live=self.live_replicas,
            requests_done=len(self.completed),
            in_flight=len(self._journal),
            failovers=self.failovers,
            tokens_out=int(sum(r["new_tokens"]
                               for r in self.completed)),
            per_replica=per_replica,
        )
        if meter.enabled():
            # Abacus rollup: all in-process engines share one module
            # meter, so the singleton's ledgers already cover the fleet
            out["meter"] = meter.summary()
        if audit.enabled():
            out["audit"] = audit.summary()
        return out
