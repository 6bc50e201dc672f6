"""Failure detection — heartbeats over the native rendezvous store.

The reference ecosystem's failure story is ``torchrun``'s elastic agent:
a supervisor process watches workers and tears the job down (or restarts
it) when one dies or hangs (SURVEY.md §5 "Failure detection" row; §2b
"torchrun elastic agent / c10d TCPStore" row). The TPU-native equivalent
here has two halves:

- **Worker side** (:class:`HeartbeatReporter`): a daemon thread that
  writes ``hb/<incarnation>/<rank> -> monotonic-ish wall time`` into the
  job's store every ``interval`` seconds. :func:`maybe_start_heartbeat`
  is called from :func:`runtime.bootstrap.initialize`, so any worker
  launched by the elastic agent heartbeats automatically. Two modes:

  - *liveness* (default): the thread beats as long as the process is
    up — catches crashed-but-not-exited and SIGSTOP-frozen workers.
  - *progress watchdog* (``progress_window_s`` set, from the agent's
    ``--progress-timeout``): once armed by the first
    :func:`notify_progress` call, the thread goes silent unless
    application code has called :func:`notify_progress` within the
    window (before that it beats as pure liveness, so an arbitrarily
    long first-step trace+compile is not mistaken for a hang). The
    training loop calls it once per completed step, so a worker whose
    main thread is stuck inside a hung collective stops beating even
    though the daemon thread itself is fine — this is what makes a
    deadlocked ``psum`` detectable at all (the daemon thread alone
    would happily beat forever under it).

- **Supervisor side** (:class:`FailureDetector`): polls those keys and
  reports still-running ranks whose last beat is older than
  ``timeout`` — the hang detector that exit-code monitoring alone
  cannot provide (a deadlocked collective never exits).

Both halves speak to the C++ store (native/store.cpp) through the ctypes
bindings in :mod:`runtime.native`; the store is the same one used for
rank rendezvous, so no extra service is needed.
"""

from __future__ import annotations

import logging
import os
import random
import signal
import threading
import time

from . import native
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.obs.registry import get_registry

log = logging.getLogger(__name__)


def count_store_error(op: str) -> None:
    """One transient store-op failure absorbed as a counted retry
    (``store_errors_total{op}``) instead of a dead daemon thread or a
    silent drop — the heartbeat/publisher hardening contract. ``op``
    names the caller's operation (``beat``, ``publish``, ``dump_poll``),
    not the wire verb."""
    get_registry().counter(
        "store_errors_total",
        "transient store failures absorbed as counted retries",
        labels=("op",)).inc(op=op)


_RAISE = object()  # store_call sentinel: re-raise on deadline


def store_call(fn, *, op: str, deadline_s: float = 5.0,
               base_s: float = 0.01, max_s: float = 0.25,
               seed: int = 0, on_retry=None, fallback=_RAISE):
    """THE counted retry helper for one store operation on a path that
    must survive a partition window (the KV transfer wire, daemon
    publish loops): call ``fn()`` until it returns, retrying
    ``OSError``/``TimeoutError`` with exponential backoff + seeded
    jitter, each failure counted in ``store_errors_total{op}``.

    Semantics:

    - every failed attempt bumps ``store_errors_total{op}`` and (when
      given) calls ``on_retry()`` — the hook kv_wire uses to bump its
      own ``kv_wire_retries_total{op}`` without a second ``except``
      site (the lint contract: this function is the only
      ``except OSError`` on the transfer path);
    - backoff is ``min(base_s * 2**attempt, max_s)`` scaled by a
      jitter factor in ``[0.5, 1.5)`` drawn from a ``random.Random``
      seeded by ``(seed, op)`` — deterministic per (seed, op) stream,
      so a rerun retries on the same schedule;
    - ``deadline_s`` bounds the whole call: once it elapses the last
      error re-raises to the caller — or, when ``fallback=`` is given,
      returns that value instead, which is how callers own graceful
      degradation (kv_wire's pull passes ``fallback=None`` and turns a
      dead wire into a cold re-prefill — a bounded failure, never a
      wedged request) without growing a second ``except`` site.
    """
    rng = random.Random((int(seed) << 16) ^ (hash(op) & 0xFFFF))
    deadline = time.monotonic() + float(deadline_s)
    attempt = 0
    while True:
        try:
            return fn()
        except (OSError, TimeoutError):
            count_store_error(op)
            if on_retry is not None:
                on_retry()
            now = time.monotonic()
            if now >= deadline:
                if fallback is not _RAISE:
                    return fallback
                raise
            delay = min(base_s * (2.0 ** attempt), max_s)
            delay *= 0.5 + rng.random()
            time.sleep(min(delay, max(deadline - now, 0.0)))
            attempt += 1

# Environment contract between the elastic agent and its workers.
ENV_STORE_PORT = "TPUNN_STORE_PORT"
ENV_STORE_HOST = "TPUNN_STORE_HOST"
ENV_RESTART = "TPUNN_RESTART"          # incarnation index (0 on first launch)
ENV_HB_INTERVAL = "TPUNN_HEARTBEAT_INTERVAL"
ENV_PROGRESS_WINDOW = "TPUNN_PROGRESS_WINDOW"
ENV_PREEMPT = "TPUNN_PREEMPT"  # "1" forces preemption handling on

# Worker exit code for a *graceful* preemption exit (SIGTERM → finish
# the in-flight step → synchronous checkpoint save → exit). The elastic
# agent restarts on it WITHOUT charging the restart budget — a
# preempted worker did nothing wrong. Distinct from chaos.CRASH_EXIT_CODE
# and outside the 128+N signal-kill convention.
GRACEFUL_EXIT_CODE = 83


def _hb_key(incarnation: int, rank: int) -> str:
    return f"hb/{incarnation}/{rank}"


def _flight_dump_key(incarnation: int) -> str:
    """Supervisor→worker flight-dump request over the heartbeat store.
    The heartbeat daemon thread serves it — the one thread guaranteed
    alive when the main thread is wedged inside a hung collective."""
    return f"flight/dump/{incarnation}"


class HeartbeatReporter:
    """Worker-side daemon thread: periodic ``set(hb/<inc>/<rank>, now)``.

    With ``progress_window_s`` set, beats are suppressed once
    :meth:`notify_progress` has not been called for that long (progress
    watchdog mode — see module docstring).
    """

    def __init__(self, client: native.StoreClient, *, rank: int,
                 incarnation: int = 0, interval_s: float = 1.0,
                 progress_window_s: float | None = None) -> None:
        self._client = client
        self.rank = rank
        self.incarnation = incarnation
        self._key = _hb_key(incarnation, rank)
        self._dump_key = _flight_dump_key(incarnation)
        self._dump_served = False
        self._was_suppressed = False
        self._interval = interval_s
        self._window = progress_window_s
        # observability counters (obs/runtime_gauges.py reads these):
        # beats written, beats withheld by the watchdog, last beat time
        self._beats = 0
        self._suppressed = 0
        self.store_errors = 0  # beats absorbed as counted retries
        self._last_beat: float | None = None
        # None until the first notify_progress: the watchdog only arms
        # once a step has completed, so an arbitrarily long first-step
        # trace+compile can't read as a hang and livelock the restarts
        # (until then, beats are pure process liveness).
        self._last_progress: float | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"heartbeat-r{rank}", daemon=True
        )
        self.beat()  # one synchronous beat so the detector sees us at once
        self._thread.start()

    @property
    def client(self) -> native.StoreClient:
        """The live store connection (obs/aggregate.py publishes
        snapshots through it — same handle, thread-safe)."""
        return self._client

    def beat(self) -> None:
        now = time.time()
        self._client.set(self._key, repr(now).encode())
        self._beats += 1
        self._last_beat = now

    def stats(self) -> dict:
        """Liveness counters for the metric registry: seconds since the
        last beat, beats written, watchdog-suppressed beats."""
        now = time.time()
        return {
            "age_s": (now - self._last_beat
                      if self._last_beat is not None else -1.0),
            "beats": self._beats,
            "suppressed": self._suppressed,
            "store_errors": self.store_errors,
        }

    def notify_progress(self) -> None:
        """Application-level liveness: the step loop moved forward."""
        self._last_progress = time.time()

    def disarm(self) -> None:
        """Back to liveness-only (training loop exited): post-loop work
        of unbounded length — checkpoint drains, eval — must not read
        as a hang."""
        self._last_progress = None

    def _maybe_serve_dump_request(self) -> None:
        """Serve a supervisor-initiated flight-dump request (launch.py
        sets the key when FailureDetector sees stale ranks). Runs on
        this daemon thread precisely because the main thread may be
        stuck inside the hung collective being diagnosed."""
        if self._dump_served:
            return
        try:
            if not self._client.check(self._dump_key):
                return
            reason = self._client.get(
                self._dump_key, timeout_ms=1000).decode("utf-8", "replace")
        except (OSError, TimeoutError):
            count_store_error("dump_poll")
            return
        self._dump_served = True
        flight.dump_now(f"supervisor:{reason}", force=True)

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                self._maybe_serve_dump_request()
            except Exception:  # a dump must never kill the beat thread
                log.exception("flight dump request handling failed")
            if (self._window is not None
                    and self._last_progress is not None
                    and time.time() - self._last_progress > self._window):
                if not self._was_suppressed:
                    # first watchdog trip: the main loop stopped making
                    # progress — capture the ring NOW, while the hung
                    # collective is still the newest entry
                    self._was_suppressed = True
                    flight.dump_now("progress_watchdog")
                self._suppressed += 1
                continue  # main thread looks stuck: go silent, get flagged
            self._was_suppressed = False
            try:
                self.beat()
            except (OSError, TimeoutError):
                # Transient store failure (partition, flake, a
                # supervisor mid-teardown): a missed beat must degrade
                # to a counted retry, never kill this thread — a beat
                # thread that died during a 500 ms partition would
                # leave a perfectly healthy worker reading as hung
                # forever after. A store that is truly gone keeps the
                # counter climbing while the supervisor-side staleness
                # math does its job.
                self.store_errors += 1
                count_store_error("beat")

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2 * self._interval)
        if self._thread.is_alive():
            # Beat thread is wedged inside a store call; closing now
            # would free the C handle under it. Leak the connection —
            # the process is exiting anyway.
            return
        self._client.close()


_reporter: HeartbeatReporter | None = None


def maybe_start_heartbeat(rank: int | None = None) -> HeartbeatReporter | None:
    """Start heartbeating iff launched under the elastic agent.

    Reads the agent's env contract; a plain (non-agent) launch has no
    ``TPUNN_STORE_PORT`` and this is a no-op. Idempotent. Under the
    agent a native library that cannot be built raises
    (:class:`native.NativeUnavailable`): the agent is listening for
    beats, and a worker that carried on without them would be killed
    as hung.
    """
    global _reporter
    if _reporter is not None:
        return _reporter
    port = os.environ.get(ENV_STORE_PORT)
    if not port:
        return None
    if rank is None:
        rank = int(os.environ.get("PROCESS_ID", os.environ.get("RANK", "0")))
    window = os.environ.get(ENV_PROGRESS_WINDOW)
    try:
        client = native.StoreClient(
            os.environ.get(ENV_STORE_HOST, "127.0.0.1"), int(port)
        )
        # OSError can come from the constructor's first beat when the
        # agent is tearing the store down at this very moment; a dying
        # job must not gain a worker traceback on top.
        _reporter = HeartbeatReporter(
            client,
            rank=rank,
            incarnation=int(os.environ.get(ENV_RESTART, "0")),
            interval_s=float(os.environ.get(ENV_HB_INTERVAL, "1.0")),
            progress_window_s=float(window) if window else None,
        )
    except (ConnectionError, OSError) as e:
        log.warning("heartbeat disabled: %s", e)
        return None
    # flight-recorder dump triggers ride the agent contract: fatal
    # signals + unhandled exceptions dump the ring, and the flight
    # watchdog dumps when no event lands for a progress window (a
    # collective that never completes stops the event stream)
    flight.install_crash_hooks()
    if window:
        flight.start_watchdog(float(window))
    return _reporter


def reporter() -> HeartbeatReporter | None:
    """The live worker-side reporter, if the agent started one."""
    return _reporter


def heartbeat_stats() -> dict | None:
    """This worker's liveness counters; None outside the agent."""
    return _reporter.stats() if _reporter is not None else None


def notify_progress() -> None:
    """Per-step hook for training loops; no-op outside the agent."""
    if _reporter is not None:
        _reporter.notify_progress()


def notify_done() -> None:
    """Loop-exit hook: disarm the progress watchdog; no-op outside the
    agent."""
    if _reporter is not None:
        _reporter.disarm()


# ---------------------------------------------------------------------------
# Worker-side preemption handling (SIGTERM → cooperative graceful exit)
# ---------------------------------------------------------------------------

_preempt_flag = threading.Event()
_preempt_prev_handler = None
_preempt_installed = False


def install_preemption_handler(force: bool = False) -> bool:
    """SIGTERM becomes a *preemption notice* instead of an immediate
    kill: the handler only sets a flag (and snapshots the flight ring);
    the training loop notices it at the next step boundary, forces a
    synchronous checkpoint save, and exits ``GRACEFUL_EXIT_CODE``.

    Installed only when it can matter: under the elastic agent
    (``TPUNN_STORE_PORT`` set — the agent classifies the graceful code)
    or when ``TPUNN_PREEMPT=1`` / ``force`` asks for it (bare runs on
    preemptible VMs). Main-thread only (signal API constraint);
    idempotent. Returns True when the handler is active."""
    global _preempt_installed, _preempt_prev_handler
    if _preempt_installed:
        return True
    if not force and not os.environ.get(ENV_STORE_PORT) \
            and os.environ.get(ENV_PREEMPT, "0") != "1":
        return False

    def _handler(signum, frame):
        # flag-only + ring snapshot: no locks we might already hold
        # beyond what the flight dump path has always taken
        _preempt_flag.set()
        try:
            flight.dump_now("preempt:SIGTERM", force=True)
        except Exception:
            pass

    try:
        _preempt_prev_handler = signal.signal(signal.SIGTERM, _handler)
    except ValueError:  # not the main thread
        return False
    _preempt_installed = True
    return True


def uninstall_preemption_handler() -> None:
    """Restore the previous SIGTERM disposition (Trainer.close)."""
    global _preempt_installed, _preempt_prev_handler
    if not _preempt_installed:
        return
    try:
        signal.signal(signal.SIGTERM, _preempt_prev_handler)
    except (ValueError, TypeError):
        pass
    _preempt_installed = False
    _preempt_prev_handler = None
    _preempt_flag.clear()


def preempt_requested() -> bool:
    """True once a preemption notice (SIGTERM) has arrived."""
    return _preempt_flag.is_set()


def request_preemption() -> None:
    """Programmatic preemption notice (tests / cluster integrations that
    learn about preemption out-of-band rather than via SIGTERM)."""
    _preempt_flag.set()


class FailureDetector:
    """Supervisor-side staleness check over the workers' heartbeat keys.

    Node-local by design: each elastic agent hosts its own store and
    watches only the ranks it spawned (crashes/hangs on other nodes are
    that node's agent's job; cross-node teardown rides the job-level
    restart because a killed gang takes the JAX coordinator down with
    it).
    """

    def __init__(self, client: native.StoreClient, *, ranks: list[int],
                 incarnation: int, timeout_s: float) -> None:
        self._client = client
        self._ranks = list(ranks)
        self._incarnation = incarnation
        self._timeout = timeout_s
        self._first_seen: dict[int, float] = {}
        # rank -> number of times it has been reported stale (the
        # supervisor-side missed-beat gauge, obs/runtime_gauges.py)
        self.missed_counts: dict[int, int] = {r: 0 for r in self._ranks}

    def any_beats(self) -> bool:
        """Whether ANY watched rank has ever heartbeaten this
        incarnation — the restart policy's fail-fast discriminator
        (a gang that died before its first beat is a startup crash,
        not a mid-training fault)."""
        try:
            return any(a is not None
                       for a in self.last_beat_ages().values())
        except OSError:
            return False

    def last_beat_ages(self) -> dict[int, float | None]:
        """Per-rank seconds since the last beat (None = never beaten) —
        the raw staleness signal behind :meth:`stale_ranks`, exported
        as gauges by obs/runtime_gauges.export_detector_gauges."""
        now = time.time()
        ages: dict[int, float | None] = {}
        for rank in self._ranks:
            key = _hb_key(self._incarnation, rank)
            if self._client.check(key):
                ages[rank] = now - float(
                    self._client.get(key, timeout_ms=1000))
            else:
                ages[rank] = None
        return ages

    def request_flight_dump(self, reason: str) -> bool:
        """Ask every worker to dump its flight ring (served by each
        worker's heartbeat daemon thread — see
        :meth:`HeartbeatReporter._maybe_serve_dump_request`). Called by
        the agent when stale ranks are detected, BEFORE the gang is
        killed. Returns False when the store write fails (a dying store
        must not mask the hang report)."""
        try:
            self._client.set(_flight_dump_key(self._incarnation),
                             reason.encode())
            return True
        except OSError as e:
            log.warning("flight dump request failed: %s", e)
            return False

    def stale_ranks(self, alive: set[int] | None = None) -> list[int]:
        """Ranks whose heartbeat is older than the timeout.

        ``alive`` — ranks whose process is still running; ranks not in
        it have exited and are the exit-code watcher's business, not
        ours (a worker that finished cleanly stops beating and must not
        read as hung). A rank that has never beaten is only stale once
        it has been up longer than the timeout (startup grace: workers
        need time to import jax and connect).
        """
        now = time.time()
        stale = []
        for rank in self._ranks:
            if alive is not None and rank not in alive:
                continue
            key = _hb_key(self._incarnation, rank)
            if self._client.check(key):
                last = float(self._client.get(key, timeout_ms=1000))
                if now - last > self._timeout:
                    stale.append(rank)
            else:
                first = self._first_seen.setdefault(rank, now)
                if now - first > self._timeout:
                    stale.append(rank)
        for rank in stale:
            self.missed_counts[rank] = self.missed_counts.get(rank, 0) + 1
        return stale
