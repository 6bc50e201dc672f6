"""One listener for what JAX, and the collector, do on a thread.

A call of a jitted function that finds no executable traces the Python
function, lowers the jaxpr to a module and compiles it (or loads it
from the persistent cache), all on the calling thread and all before
the call returns: to a serve loop that is a stall of its one thread, to
a trainer a step that took seconds. JAX reports each stage through
``jax.monitoring`` with the function's name (``fun_name``), on the
thread that did the work. :func:`install` registers one set of
listeners for the process, once, and keeps

- registry counters ``jit_stage_seconds_total{stage,fun}`` and
  ``jit_stage_events_total{stage,fun}`` (``stage`` one of ``trace``,
  ``lower``, ``compile``; at most :data:`MAX_FUNS` names, the rest
  under ``other``), ``jit_cache_events_total{result}`` (``hit``,
  ``miss``: the persistent cache) and
  ``jit_cache_retrieval_seconds_total``;
- running totals a thread (:func:`thread_totals`), which a span or a
  loop's meter reads before and after a piece of work: the difference
  is what that work spent in JAX's front end;
- a bounded ring of the last events (:func:`events`): the end on
  ``time.monotonic()``, stage, function, seconds, thread. Events are
  rare, so the ring reaches back past warm-up, and a reader cuts a
  window out of it by its own stamps.

A jitted function traced inside another's trace (every ``jnp``
operation is one) reports too, before the outer one does. JAX also
announces each stage's start, so the listener keeps a stack a thread:
a nested event adds its seconds to its own stage's total and takes
them out of the enclosing event's, is counted under ``fun="(nested)"``
and stays out of the ring. The totals therefore add up to wall time,
the ring's events of one thread never overlap, and the names that
arrive last, the program's own, always find room among the labels.

It also times the garbage collector (``gc.callbacks``): a full
collection stops every thread and belongs to no span. The callback
touches plain numbers only (it can run inside any allocation, under
any lock); :func:`publish` brings ``gc_pause_seconds_total{generation}``
and ``gc_collections_total{generation}`` up to date.

Nothing here runs in steady state: the listeners fire when JAX traces,
the callback when the collector runs. This module imports jax inside
:func:`install` only.
"""

from __future__ import annotations

import collections
import gc
import logging
import threading
import time
from typing import Callable, NamedTuple

from pytorch_distributed_nn_tpu.obs import scopes
from pytorch_distributed_nn_tpu.obs import span as _span
from pytorch_distributed_nn_tpu.obs.registry import get_registry

log = logging.getLogger(__name__)

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
NESTED = "(nested)"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# distinct ``fun`` label values; a program that mints names (a lambda a
# request) lands under "other" beyond them
MAX_FUNS = 64
RING = 4096


class JitEvent(NamedTuple):
    t: float          # end, time.monotonic()
    stage: str        # trace | lower | compile
    fun: str
    seconds: float
    tid: int
    thread: str


class ThreadTotals:
    """What one thread has spent in JAX's front end so far. ``total``
    moves whenever any stage does, so one comparison says whether a
    piece of work traced."""

    __slots__ = ("trace", "lower", "compile", "total", "fun", "events",
                 "cache_hits", "cache_misses", "_stack")

    def __init__(self) -> None:
        self.trace = self.lower = self.compile = self.total = 0.0
        self.fun = ""
        self.events = 0
        self.cache_hits = self.cache_misses = 0
        self._stack: list = []   # seconds nested in each open stage

    def mark(self) -> tuple:
        """The totals now; the last entry is ``total``."""
        return (self.trace, self.lower, self.compile, self.cache_hits,
                self.cache_misses, self.total)

    def since(self, mark: tuple) -> dict:
        """Seconds by stage and cache traffic since ``mark``, with the
        last function seen."""
        return dict(trace=self.trace - mark[0], lower=self.lower - mark[1],
                    compile=self.compile - mark[2], fun=self.fun,
                    cache_hits=self.cache_hits - mark[3],
                    cache_misses=self.cache_misses - mark[4])


class GcTotals:
    """Collector seconds of the process, by generation."""

    __slots__ = ("seconds", "by_gen", "collections", "_t0", "_published")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.by_gen = [0.0, 0.0, 0.0]
        self.collections = [0, 0, 0]
        self._t0 = 0.0
        self._published = ([0.0, 0.0, 0.0], [0, 0, 0])


def bare_name(fun: str) -> str:
    """``jit(_serve_prefill)`` (lower, compile) and ``_serve_prefill``
    (trace) are one function."""
    if fun.startswith("jit(") and fun.endswith(")"):
        return fun[4:-1]
    return fun


class _Watch:
    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.threads: dict[int, ThreadTotals] = {}
        self.ring: collections.deque = collections.deque(maxlen=RING)
        self.gc = GcTotals()
        self.funs: set[str] = set()
        self.sinks: list[Callable] = []
        self.loop_threads: dict[int, str] = {}
        self.cache_hits = self.cache_misses = 0
        self.compile_s = 0.0

    # -- jax.monitoring ----------------------------------------------------

    def totals(self, tid: int) -> ThreadTotals:
        tot = self.threads.get(tid)
        if tot is None:
            with self.lock:
                tot = self.threads.setdefault(tid, ThreadTotals())
        return tot

    def on_start(self, event: str, value: float, **_) -> None:
        """A stage begins on this thread (JAX records its start time as
        a scalar under the stage's own name)."""
        if event in STAGES:
            self.totals(threading.get_ident())._stack.append(0.0)

    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "", **_) -> None:
        stage = STAGES.get(event)
        if stage is None:
            return
        now = time.monotonic()
        seconds = end - start
        th = threading.current_thread()
        tot = self.totals(th.ident)
        stack = tot._stack
        self_s = max(seconds - (stack.pop() if stack else 0.0), 0.0)
        setattr(tot, stage, getattr(tot, stage) + self_s)
        tot.total += self_s
        tot.events += 1
        if stage == "compile":
            self.compile_s += seconds
        fun = bare_name(str(fun_name))
        if stack:
            stack[-1] += seconds
            label = NESTED
        else:
            tot.fun = fun
            self.ring.append(JitEvent(now, stage, fun, seconds, th.ident,
                                      th.name))
            label = self._label(fun)
        reg = get_registry()
        reg.counter("jit_stage_seconds_total",
                    "seconds a jitted call spent tracing, lowering or "
                    "compiling, nested stages included",
                    labels=("stage", "fun")).inc(seconds, stage=stage,
                                                 fun=label)
        reg.counter("jit_stage_events_total",
                    "calls that traced, lowered or compiled",
                    labels=("stage", "fun")).inc(stage=stage, fun=label)
        if stack:
            return
        for sink in list(self.sinks):
            try:
                sink(stage, fun, seconds)
            except Exception:  # a telemetry sink must never break a call
                log.exception("jit event sink %r failed", sink)

    def _label(self, fun: str) -> str:
        if fun in self.funs:
            return fun
        with self.lock:
            if len(self.funs) < MAX_FUNS:
                self.funs.add(fun)
                return fun
        return "other"

    def on_event(self, event: str, **_) -> None:
        result = _CACHE.get(event)
        if result is None:
            return
        tot = self.totals(threading.get_ident())
        if result == "hit":
            tot.cache_hits += 1
            self.cache_hits += 1
        else:
            tot.cache_misses += 1
            self.cache_misses += 1
        get_registry().counter(
            "jit_cache_events_total", "persistent compile cache lookups",
            labels=("result",)).inc(result=result)

    def on_duration(self, event: str, seconds: float, **_) -> None:
        if event == _RETRIEVAL:
            get_registry().counter(
                "jit_cache_retrieval_seconds_total",
                "seconds spent reading executables from the persistent "
                "compile cache").inc(seconds)

    # -- gc ----------------------------------------------------------------

    def on_gc(self, phase: str, info: dict) -> None:
        g = self.gc
        if phase == "start":
            g._t0 = time.perf_counter()
        elif g._t0:
            dt = time.perf_counter() - g._t0
            g._t0 = 0.0
            gen = min(int(info.get("generation", 2)), 2)
            g.seconds += dt
            g.by_gen[gen] += dt
            g.collections[gen] += 1


_watch: _Watch | None = None
_install_lock = threading.Lock()
_ZERO = ThreadTotals()   # what an uninstalled process reads: never moves


def install() -> None:
    """Register the listeners and the collector's callback (idempotent:
    one set a process, however many engines and trainers are built)."""
    global _watch
    if _watch is not None:
        return
    with _install_lock:
        if _watch is not None:
            return
        import jax.monitoring as mon

        w = _Watch()
        mon.register_scalar_listener(w.on_start)
        mon.register_event_time_span_listener(w.on_span)
        mon.register_event_listener(w.on_event)
        mon.register_event_duration_secs_listener(w.on_duration)
        gc.callbacks.append(w.on_gc)
        _watch = w


def installed() -> bool:
    return _watch is not None


def thread_totals(tid: int | None = None) -> ThreadTotals:
    """The running totals of a thread (default: the caller's). Hold on
    to the object: it is updated in place."""
    w = _watch
    if w is None:
        return _ZERO
    return w.totals(threading.get_ident() if tid is None else tid)


def gc_totals() -> GcTotals:
    w = _watch
    return w.gc if w is not None else GcTotals()


def process_totals() -> dict:
    """Backend-compile seconds (inclusive, every thread) and persistent
    cache traffic since :func:`install`."""
    w = _watch
    if w is None:
        return dict(compile_s=0.0, cache_hits=0, cache_misses=0)
    return dict(compile_s=w.compile_s, cache_hits=w.cache_hits,
                cache_misses=w.cache_misses)


def events(t0: float = float("-inf"), t1: float = float("inf")) -> list:
    """The ring's events that ended in ``[t0, t1)`` on
    ``time.monotonic()``, oldest first."""
    w = _watch
    if w is None:
        return []
    return [e for e in list(w.ring) if t0 <= e.t < t1]


def mark_loop_thread() -> None:
    """The calling thread runs a loop users wait on (a serve loop, a
    trainer's): a reader tells its events from a helper thread's."""
    w = _watch
    if w is not None:
        th = threading.current_thread()
        w.loop_threads[th.ident] = th.name


def loop_threads() -> dict:
    w = _watch
    return dict(w.loop_threads) if w is not None else {}


def add_sink(fn: Callable) -> None:
    """``fn(stage, fun, seconds)`` on every event, on the thread that
    traced (obs/xray.py's compile telemetry)."""
    install()
    if fn not in _watch.sinks:
        _watch.sinks.append(fn)


def remove_sink(fn: Callable) -> bool:
    """Returns whether ``fn`` was listening."""
    w = _watch
    if w is not None and fn in w.sinks:
        w.sinks.remove(fn)
        return True
    return False


def publish() -> None:
    """Bring the collector's registry counters up to date (the callback
    itself takes no lock)."""
    w = _watch
    if w is None:
        return
    g = w.gc
    reg = get_registry()
    secs = reg.counter("gc_pause_seconds_total",
                       "seconds the garbage collector held every thread",
                       labels=("generation",))
    runs = reg.counter("gc_collections_total", "garbage collections",
                       labels=("generation",))
    seen_s, seen_n = g._published
    with w.lock:   # a trainer's flush and a serve loop's may meet here
        for gen in range(3):
            ds = g.by_gen[gen] - seen_s[gen]
            dn = g.collections[gen] - seen_n[gen]
            if dn > 0:
                secs.inc(max(ds, 0.0), generation=gen)
                runs.inc(dn, generation=gen)
                seen_s[gen] += ds
                seen_n[gen] += dn


class _WatchedSpan:
    """An armed span around a dispatch of a compiled program: at exit,
    if the thread traced, lowered or compiled inside it, the span says
    so in late arguments, and ``program`` (``(fn, args)`` or ``(fn,
    args, kwargs)`` of the call inside it) is noted for the map from
    instruction to scope (:func:`obs.scopes.note`): a call that traced
    is the one moment a new variant of a program appears."""

    __slots__ = ("_span", "_tot", "_mark", "_program")

    def __init__(self, span, program=None) -> None:
        self._span = span
        self._program = program

    def __enter__(self):
        self._tot = thread_totals()
        self._mark = self._tot.mark()
        self._span.__enter__()
        return self

    def set(self, **args) -> None:
        self._span.set(**args)

    def __exit__(self, *exc):
        tot = self._tot
        if tot.total != self._mark[-1]:
            d = tot.since(self._mark)
            self._span.set(trace_ms=round(d["trace"] * 1e3, 3),
                           lower_ms=round(d["lower"] * 1e3, 3),
                           compile_ms=round(d["compile"] * 1e3, 3),
                           jit_fun=d["fun"])
            if self._program is not None and exc[0] is None:
                scopes.note(*self._program)
        return self._span.__exit__(*exc)


def dispatch_span(name: str, cat: str = "app", program=None, **args):
    """:func:`obs.span` for a span that holds a dispatch: armed, it
    gains ``trace_ms``, ``lower_ms``, ``compile_ms`` and ``jit_fun``
    when the call inside it did not find its executable; unarmed and
    without a ``program`` it is the shared null context and reads
    nothing. With a ``program`` it watches the thread's totals either
    way (one mark, one comparison), and notes the program if the call
    traced."""
    sp = _span.span(name, cat, **args)
    if sp is _span._NULL and program is None:
        return sp
    return _WatchedSpan(sp, program)


def noting(program):
    """:func:`dispatch_span` for a dispatch that has no span of its
    own: nothing is written, and ``program`` is noted if the call
    inside it traced."""
    return _WatchedSpan(_span._NULL, program)


watched = _WatchedSpan
