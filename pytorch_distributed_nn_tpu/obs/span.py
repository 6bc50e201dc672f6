"""Span tracing: one boundary, two sinks.

``with obs.span("data/next_batch"): ...`` marks a host-side phase. With
neither sink armed (the default) a span costs one module-global read and
one call that asks the profiler whether a session is running, and yields
a shared null context — no allocation, no clock read — so
instrumentation can stay in the hot loop permanently.

Sink 1, the profiler. While a ``jax.profiler`` session is running
(``start_trace``, an xray capture, the benchmark's traced run) a span
is written into it as a ``jax.profiler.TraceAnnotation`` with the
span's name and its keyword arguments as stats. It lands in the
session's ``.xplane.pb`` on the ``/host:CPU`` plane, on the line of the
thread that ran it, on the timeline the device planes use; nesting on
one thread is the parent link. Nobody arms this sink: starting a
profile does. It is recorded at ``host_tracer_level`` 1 and up.

Sink 2, the recorder. Enabled (:func:`enable_tracing`), spans record
complete events (``ph: "X"``, microsecond ``ts``/``dur``) into an
in-memory buffer that :func:`write_trace` serializes as Chrome
trace-event JSON: a host-only timeline that needs no profiler.
:func:`merge_chrome_traces` concatenates such files for a viewer.

Thread-safe: producer threads (data prefetch) trace under the same
recorder; ``tid`` keeps their tracks apart.

This module imports no jax: spans stay usable before and without a
backend. The profiler is looked up in ``sys.modules`` once the program
has imported jax itself.
"""

from __future__ import annotations

import gzip
import json
import sys
import threading
import time
from pathlib import Path


class _NullSpan:
    """Reusable disabled-tracing context (one instance, no state)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        """Arguments known only at the span's end; dropped here."""


_NULL = _NullSpan()


class TraceRecorder:
    """In-memory trace-event buffer for one process."""

    def __init__(self, *, process_index: int = 0,
                 process_name: str | None = None) -> None:
        self.process_index = process_index
        self.process_name = process_name or f"host{process_index}"
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._t0 = time.perf_counter()
        # wall-clock anchor so merged traces share an epoch
        self.epoch_unix = time.time()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def add_event(self, name: str, ts_us: float, dur_us: float,
                  cat: str = "app", args: dict | None = None) -> None:
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": ts_us, "dur": dur_us,
              "pid": self.process_index,
              "tid": threading.get_ident() & 0xFFFF}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, cat: str = "app",
                args: dict | None = None) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self._now_us(), "pid": self.process_index,
              "tid": threading.get_ident() & 0xFFFF}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def trace_json(self) -> dict:
        meta = [{"name": "process_name", "ph": "M",
                 "pid": self.process_index,
                 "args": {"name": self.process_name}}]
        return {"traceEvents": meta + self.events(),
                "displayTimeUnit": "ms",
                "otherData": {"epoch_unix": self.epoch_unix}}


class _Span:
    """A span with at least one sink armed: ``_rec`` the recorder or
    None, ``_ann`` the profiler's annotation or None."""

    __slots__ = ("_rec", "_ann", "_name", "_cat", "_args", "_t0")

    def __init__(self, rec: TraceRecorder | None, annotation, name: str,
                 cat: str, args: dict) -> None:
        self._rec = rec
        self._ann = annotation(name, **args) \
            if annotation is not None else None
        self._name = name
        self._cat = cat
        self._args = args

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if self._rec is not None:
            self._t0 = self._rec._now_us()
        return self

    def set(self, **args):
        """Add arguments known only at the span's end (a count of what
        it did). Call inside the ``with`` block."""
        if self._ann is not None:
            self._ann.set_metadata(**args)
        if self._rec is not None:
            self._args = {**self._args, **args}

    def __exit__(self, *exc):
        if self._rec is not None:
            t1 = self._rec._now_us()
            self._rec.add_event(self._name, self._t0, t1 - self._t0,
                                self._cat, self._args or None)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


_recorder: TraceRecorder | None = None
# jax.profiler.TraceAnnotation, once the program has imported jax
_annotation = None


def _session_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is
    recording host events, else None."""
    global _annotation
    ann = _annotation
    if ann is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return None
        ann = _annotation = profiler.TraceAnnotation
    return ann if ann.is_enabled() else None


def span(name: str, cat: str = "app", **args):
    """Context manager marking a host-side phase. Written to the running
    profiler session, to the recorder, to both, or (neither armed) to
    nothing: then it is the shared null context."""
    rec = _recorder
    ann = _session_annotation()
    if rec is None and ann is None:
        return _NULL
    return _Span(rec, ann, name, cat, args)


def tracing_enabled() -> bool:
    return _recorder is not None


def current_recorder() -> TraceRecorder | None:
    """The live recorder, or None when tracing is off — for callers
    that add retroactive events (e.g. serve's per-request spans, whose
    duration is only known at retirement) without forcing tracing on
    the way ``enable_tracing`` would."""
    return _recorder


def enable_tracing(*, process_index: int | None = None) -> TraceRecorder:
    """Start recording spans (idempotent: returns the live recorder).

    ``process_index`` defaults to ``jax.process_index()`` when jax is
    already imported, else 0 — span.py itself never imports jax (spans
    must stay usable before/without a backend).
    """
    global _recorder
    if _recorder is not None:
        return _recorder
    if process_index is None:
        import sys

        jax = sys.modules.get("jax")
        process_index = jax.process_index() if jax is not None else 0
    _recorder = TraceRecorder(process_index=process_index)
    return _recorder


def disable_tracing() -> TraceRecorder | None:
    """Stop recording; returns the recorder (with its buffered events)."""
    global _recorder
    rec = _recorder
    _recorder = None
    return rec


def write_trace(path, recorder: TraceRecorder | None = None) -> Path:
    """Serialize the recorder (default: the live one) as Chrome
    trace-event JSON; ``.gz`` suffix gzips — matching the xprof
    ``perfetto_trace.json.gz`` convention."""
    rec = recorder if recorder is not None else _recorder
    if rec is None:
        raise RuntimeError("tracing is not enabled and no recorder given")
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    payload = json.dumps(rec.trace_json())
    if p.suffix == ".gz":
        with gzip.open(p, "wt") as f:
            f.write(payload)
    else:
        p.write_text(payload)
    return p


def _load_trace(path) -> list[dict]:
    p = Path(path)
    opener = gzip.open if p.suffix == ".gz" else open
    with opener(p, "rt") as f:
        tr = json.load(f)
    return tr["traceEvents"] if isinstance(tr, dict) else tr


def merge_chrome_traces(paths, out) -> Path:
    """Concatenate trace-event files (host spans + xprof perfetto device
    slices) into one Chrome trace. Each input keeps its own pid tracks;
    offset alignment is the viewer's job (both sides stamp relative
    timestamps) — the merged file is for eyeballing phase overlap, not
    sub-ms cross-clock skew. For host spans on the device's own
    timeline, start a profiler session instead: the spans are in it."""
    events: list[dict] = []
    for path in paths:
        events.extend(_load_trace(path))
    p = Path(out)
    p.parent.mkdir(parents=True, exist_ok=True)
    payload = json.dumps({"traceEvents": events, "displayTimeUnit": "ms"})
    if p.suffix == ".gz":
        with gzip.open(p, "wt") as f:
            f.write(payload)
    else:
        p.write_text(payload)
    return p
