"""Collective flight recorder: a bounded, always-on per-host ring.

PR 1 built *live* telemetry (registry, spans, goodput). This module is
the *post-mortem* counterpart: when a pod run dies — and at scale the
dominant failure is not a stack trace but a silent hang, one rank
stalled in ``psum``/``ppermute`` with every other rank blocked behind
it (the pjit-on-TPUv4 / MPMD-pipeline operational cost, PAPERS.md) —
nothing in a log says *which collective, which rank, which step*. The
flight recorder does: every process keeps the last ``capacity`` comm /
step / checkpoint / data events in a fixed-size ring, and dump
triggers (fatal signals, unhandled exceptions, the progress watchdog,
a supervisor request over the native store — see
:mod:`runtime.failure` and :mod:`launch`) write it to
``flight_rank<k>.json`` next to the run's JSONL.
``obs/forensics.py`` + ``scripts/obs_doctor.py`` merge the per-rank
dumps, align collectives by sequence, and name the first divergence.

Cost model (why it can stay always-on):

- collective records from :func:`ops.collectives._record` fire at
  *trace* time — once per compiled program, not per step;
- per-step cost is two ring appends (step marker + dispatch event): a
  lock acquire and a ``deque.append`` each, ~1 µs against millisecond
  steps;
- the ring is bounded (``deque(maxlen=...)``), so memory is O(capacity)
  forever.

Event kinds:

- ``collective`` — a comm op. ``note="trace"`` marks trace-time records
  (program structure: op/axis/bytes/shape/dtype at the step being
  traced); ``note="dispatch"`` marks host-driven runtime dispatches
  (the :func:`collective` context manager — enqueue ``t0``, complete
  ``t1``; ``t1 = None`` means *enqueued, never completed*: the smoking
  gun of a hang);
- ``dispatch`` — one fused step program handed to the device (Trainer);
- ``step`` — step-boundary marker (Trainer); per-rank step timestamps
  drive the doctor's straggler percentiles;
- ``checkpoint`` / ``data`` — save/restore and loader hand-off events;
- ``chaos`` — an injected fault (runtime/chaos.py): every TPUNN_CHAOS
  injection lands here so forensics can't misattribute it;
- ``preempt`` — preemption-notice markers (SIGTERM → graceful exit);
- ``serve`` — serving-engine lifecycle (serve/): one event per decode
  round plus admit/reject/retire/drain markers, so the doctor can see
  a wedged decode loop or shed traffic post-mortem;
- ``alert`` — a watchtower alert (obs/watchtower.py): every online
  detection lands here emit-first, and page-severity alerts trigger an
  automatic :func:`dump_now` — the ring that reaches disk already
  names what the run knew was wrong;
- ``fleet`` — replica-fleet lifecycle (serve/fleet.py): counted state
  transitions (``state:<s>``), ``replica_down`` (with the stranded
  request ids in the note), failover ``readmit`` markers, and rolling
  ``reload`` completions — a dead replica's dump names its victims;
- ``xray`` — profiler lifecycle (obs/xray.py): ``capture`` /
  ``capture_done`` markers (the note names the trigger and the capture
  directory) and per-compilation ``compile`` breadcrumbs, so a dump
  names the captures that exist for the incident;
- ``audit`` — Lighthouse output-integrity observations (obs/audit.py):
  ``fingerprint`` / ``divergence`` / ``probe`` / ``quarantine``
  markers, emit-first — a divergence dump already names the
  disagreeing replicas and the suspect.

Stdlib-only on purpose: dump paths run inside signal handlers and
heartbeat daemon threads of processes whose main thread is wedged
inside XLA — they must not touch jax.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import signal
import socket
import sys
import tempfile
import threading
import time

ENV_FLIGHT = "TPUNN_FLIGHT"          # "0" disables recording entirely
ENV_FLIGHT_DIR = "TPUNN_FLIGHT_DIR"  # where dumps land (agent contract)
ENV_FLIGHT_RING = "TPUNN_FLIGHT_RING"  # ring capacity override

DEFAULT_CAPACITY = 4096

DUMP_VERSION = 1


def flight_path(directory, rank: int) -> str:
    """The per-rank dump filename contract (doctor globs on it)."""
    return os.path.join(str(directory), f"flight_rank{rank}.json")


def default_rank() -> int:
    """This process's rank from the launch env contract (no jax import:
    dumps must work from signal handlers under a wedged main thread)."""
    for var in ("PROCESS_ID", "RANK"):
        v = os.environ.get(var)
        if v is not None:
            try:
                return int(v)
            except ValueError:
                pass
    return 0


@dataclasses.dataclass
class FlightEvent:
    """One ring entry. ``t0``/``t1`` are wall-clock (``time.time()``) so
    per-rank dumps on one host align exactly and cross-host dumps align
    to NTP precision — good enough to order steps, which is all the
    doctor needs. ``t1 is None`` = begun, never completed."""

    seq: int
    kind: str  # collective | dispatch | step | checkpoint | data
    #          # | chaos | preempt | serve | alert | fleet | xray
    #          # | audit
    op: str
    step: int
    t0: float
    t1: float | None
    axis: str = ""
    nbytes: int = 0
    shape: tuple = ()
    dtype: str = ""
    note: str = ""

    def as_dict(self) -> dict:
        return {
            "seq": self.seq, "kind": self.kind, "op": self.op,
            "step": self.step, "t0": self.t0, "t1": self.t1,
            "axis": self.axis, "nbytes": self.nbytes,
            "shape": list(self.shape), "dtype": self.dtype,
            "note": self.note,
        }


class FlightRecorder:
    """The bounded ring. Thread-safe: records come from the main loop,
    the loader producer thread, and trace-time hooks concurrently;
    dumps come from heartbeat daemon threads and signal handlers."""

    def __init__(self, capacity: int | None = None, *,
                 enabled: bool | None = None) -> None:
        if capacity is None:
            capacity = int(os.environ.get(ENV_FLIGHT_RING,
                                          DEFAULT_CAPACITY))
        if capacity < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        if enabled is None:
            enabled = os.environ.get(ENV_FLIGHT, "1") != "0"
        self.capacity = capacity
        self.enabled = enabled
        self._events: collections.deque[FlightEvent] = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self._seq = 0
        self._total = 0
        self._step = -1  # last step marker (trace-time records inherit)
        self._last_event_t: float | None = None
        self._dump_dir: str | None = None
        self._dump_reasons: list[str] = []

    # -- recording -------------------------------------------------------

    def record(self, kind: str, op: str, *, step: int | None = None,
               axis: str = "", nbytes: int = 0, shape: tuple = (),
               dtype: str = "", note: str = "",
               complete: bool = True) -> FlightEvent | None:
        """Append one event; ``complete=False`` leaves ``t1`` open for a
        later :meth:`complete` (the enqueue/complete pair)."""
        if not self.enabled:
            return None
        now = time.time()
        with self._lock:
            ev = FlightEvent(
                seq=self._seq, kind=kind, op=op,
                step=self._step if step is None else int(step),
                t0=now, t1=now if complete else None,
                axis=axis, nbytes=int(nbytes), shape=tuple(shape),
                dtype=dtype, note=note,
            )
            self._seq += 1
            self._total += 1
            self._events.append(ev)
            self._last_event_t = now
        return ev

    def complete(self, ev: FlightEvent | None) -> None:
        if ev is None or not self.enabled:
            return
        now = time.time()
        ev.t1 = now
        with self._lock:
            self._last_event_t = now

    @contextlib.contextmanager
    def collective(self, op: str, *, step: int | None = None,
                   axis: str = "", nbytes: int = 0, note: str = "dispatch",
                   **fields):
        """Host-driven collective dispatch window: enqueue on enter,
        complete on exit. A rank that hangs inside leaves ``t1=None``
        in its dump — "enqueued, never completed"."""
        ev = self.record("collective", op, step=step, axis=axis,
                         nbytes=nbytes, note=note, complete=False,
                         **fields)
        try:
            yield ev
        finally:
            self.complete(ev)

    @contextlib.contextmanager
    def dispatch(self, op: str, *, step: int | None = None,
                 note: str = ""):
        """One fused step program handed to the device (async: complete
        = dispatch returned, not device finished)."""
        ev = self.record("dispatch", op, step=step, note=note,
                         complete=False)
        try:
            yield ev
        finally:
            self.complete(ev)

    def mark_step(self, step: int, note: str = "") -> None:
        """Step-boundary marker; later trace-time collective records
        inherit this step number."""
        if not self.enabled:
            return
        with self._lock:
            self._step = int(step)
        self.record("step", "start", step=step, note=note)

    def on_collective(self, op: str, *, axis: str, nbytes: int,
                      shape: tuple = (), dtype: str = "") -> None:
        """Trace-time hook (called from ``ops.collectives._record`` and
        the fake world): records program structure, not a dispatch."""
        self.record("collective", op, axis=axis, nbytes=nbytes,
                    shape=shape, dtype=dtype, note="trace")

    # -- introspection ---------------------------------------------------

    def snapshot(self) -> list[dict]:
        with self._lock:
            return [ev.as_dict() for ev in self._events]

    def last_age_s(self) -> float | None:
        """Seconds since the last recorded event (None = never armed) —
        the progress-watchdog signal."""
        with self._lock:
            last = self._last_event_t
        return None if last is None else time.time() - last

    @property
    def total_events(self) -> int:
        return self._total

    def set_dump_dir(self, directory) -> None:
        """Default dump location ("next to the run's JSONL"); the
        agent's ``TPUNN_FLIGHT_DIR`` env wins over this."""
        self._dump_dir = str(directory)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._seq = 0
            self._total = 0
            self._step = -1
            self._last_event_t = None
            self._dump_reasons = []

    # -- dumping ---------------------------------------------------------

    def _resolve_dir(self, directory=None) -> str:
        d = (directory or os.environ.get(ENV_FLIGHT_DIR)
             or self._dump_dir)
        if d:
            return str(d)
        # Last resort is a stable tmp location, NOT the CWD: an
        # unconfigured process (tests, ad-hoc scripts) must never
        # litter whatever directory it happens to run from.
        d = os.path.join(tempfile.gettempdir(), "tpunn-flight")
        try:
            os.makedirs(d, exist_ok=True)
        except OSError:
            return tempfile.gettempdir()
        return d

    def dump(self, reason: str, *, directory=None, rank: int | None = None,
             force: bool = False) -> str | None:
        """Write ``flight_rank<k>.json``. One dump per distinct reason
        unless ``force`` (a watchdog that keeps tripping must not spin
        on disk); a later dump overwrites with fresher events and the
        accumulated reason history. Never raises — dump paths run under
        dying processes."""
        if not self.enabled:
            return None
        with self._lock:
            if reason in self._dump_reasons and not force:
                return None
            self._dump_reasons.append(reason)
            reasons = list(self._dump_reasons)
        rank = default_rank() if rank is None else rank
        path = flight_path(self._resolve_dir(directory), rank)
        payload = {
            "version": DUMP_VERSION,
            "rank": rank,
            "host": socket.gethostname(),
            "pid": os.getpid(),
            "incarnation": int(os.environ.get("TPUNN_RESTART", "0")),
            "reason": reason,
            "reasons": reasons,
            "dumped_at": time.time(),
            "capacity": self.capacity,
            "total_events": self._total,
            "dropped": max(self._total - len(self._events), 0),
            "events": self.snapshot(),
        }
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)  # readers never see a torn dump
            return path
        except OSError:
            return None


_recorder = FlightRecorder()


def get_recorder() -> FlightRecorder:
    """The process-wide ring."""
    return _recorder


def reset_recorder(capacity: int | None = None, *,
                   enabled: bool | None = None) -> FlightRecorder:
    """Swap in a fresh ring (test isolation)."""
    global _recorder
    _recorder = FlightRecorder(capacity, enabled=enabled)
    return _recorder


# module-level conveniences bound to the live recorder (late-bound so
# reset_recorder takes effect everywhere)

def record(kind: str, op: str, **kw) -> FlightEvent | None:
    return _recorder.record(kind, op, **kw)


def complete(ev: FlightEvent | None) -> None:
    _recorder.complete(ev)


def mark_step(step: int, note: str = "") -> None:
    _recorder.mark_step(step, note)


def collective(op: str, **kw):
    return _recorder.collective(op, **kw)


def dispatch(op: str, **kw):
    return _recorder.dispatch(op, **kw)


def on_collective(op: str, **kw) -> None:
    _recorder.on_collective(op, **kw)


def set_dump_dir(directory) -> None:
    _recorder.set_dump_dir(directory)


def resolve_dump_dir(directory=None) -> str:
    """Where post-mortem artifacts land right now (explicit arg >
    ``TPUNN_FLIGHT_DIR`` > :func:`set_dump_dir` > a stable tmp dir).
    Companion artifacts (xray capture dirs) use this to land next to
    the flight dump."""
    return _recorder._resolve_dir(directory)


def dump_now(reason: str, *, directory=None, force: bool = False
             ) -> str | None:
    return _recorder.dump(reason, directory=directory, force=force)


# ---------------------------------------------------------------------------
# Dump triggers: crash hooks + progress watchdog
# ---------------------------------------------------------------------------

_hooks_installed = False
_watchdog_started = False


def install_crash_hooks() -> None:
    """Dump on fatal signals (SIGTERM/SIGABRT) and unhandled
    exceptions, chaining to whatever handler was there. Idempotent.
    Signal handlers need the main thread; elsewhere only the
    excepthook installs (the supervisor-request path still covers
    signal-class deaths there)."""
    global _hooks_installed
    if _hooks_installed:
        return
    _hooks_installed = True

    prev_excepthook = sys.excepthook

    def _excepthook(tp, value, tb):
        dump_now(f"exception:{tp.__name__}", force=True)
        prev_excepthook(tp, value, tb)

    sys.excepthook = _excepthook

    for signum in (signal.SIGTERM, signal.SIGABRT):
        try:
            prev = signal.getsignal(signum)

            def _handler(got, frame, *, signum=signum, prev=prev):
                dump_now(f"signal:{signal.Signals(got).name}", force=True)
                if callable(prev) and prev not in (signal.SIG_IGN,
                                                   signal.SIG_DFL):
                    prev(got, frame)
                else:
                    signal.signal(signum, signal.SIG_DFL)
                    os.kill(os.getpid(), got)

            signal.signal(signum, _handler)
        except ValueError:
            # not the main thread: excepthook-only installation
            break


def start_watchdog(window_s: float) -> bool:
    """Daemon thread that dumps when NO flight event has been recorded
    for ``window_s`` (armed by the first event, so an arbitrarily long
    first-step trace+compile can't trip it before anything ran). One
    instance per process; dumps once (the dedupe in :meth:`dump`
    absorbs re-trips)."""
    global _watchdog_started
    if _watchdog_started or window_s <= 0:
        return False
    _watchdog_started = True

    def _run() -> None:
        poll = max(min(window_s / 4.0, 1.0), 0.05)
        while True:
            time.sleep(poll)
            age = _recorder.last_age_s()
            if age is not None and age > window_s:
                dump_now("flight_watchdog")
                return

    threading.Thread(target=_run, name="flight-watchdog",
                     daemon=True).start()
    return True
