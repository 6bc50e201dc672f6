"""Goodput accounting: decompose wall step time into phases.

The trainer can report *that* a step took 41 ms; this meter reports
where it went: ``data`` (host batch wait), ``compute`` (dispatch +
device fence), ``collective`` (trace-derived share of compute, when a
profile is available), ``checkpoint``, ``eval``, and ``other`` (the
unattributed remainder — Python loop overhead, logging). The breakdown
is what the EQuARX / pjit-scaling style of perf work needs: you cannot
shrink a phase you cannot see.

Accounting contract:

- phases are measured on the host with ``perf_counter`` inside
  :meth:`GoodputMeter.phase` blocks nested in a
  :meth:`step_start`/:meth:`step_end` window; a phase opened inside
  another takes its seconds out of the outer one (exclusive seconds:
  the trainer's phases follow one another, so its numbers are what
  they were);
- ``other = wall − Σ(measured phases)`` per step, so the published
  breakdown sums to wall by construction; ``accounted_frac`` (measured
  phases / wall) is reported alongside so "other" can never silently
  swallow the step;
- async dispatch: device execution hides behind the dispatch queue, so
  host-side "compute" is dispatch time plus whatever fence the loop
  performs (device_get of the loss at log cadence). Per-window sums are
  honest — within a window the device cannot outrun the host by more
  than the queue depth;
- the collective share cannot be host-timed inside one fused step; it
  is either trace-derived (``utils.profiling.collective_trace_seconds``
  over an xprof capture) or estimated downstream from the recorded
  ``wire_bytes_per_step`` (``ops.collectives.CommRecorder``) — the
  meter carries both so ``scripts/obs_report.py`` can cross-check one
  against the other.

The same meter serves a loop that never stops between steps (ISSUE 37:
the serve loop, ``serve/engine.py``). Such a loop names its own phases
and the span each writes, calls :meth:`GoodputMeter.start` once on its
thread and :meth:`GoodputMeter.lap` at the end of every round: the lap
closes a step and opens the next at the same instant, so the steps
partition the thread's wall time, whatever happens between two rounds
landing in the later one. Each lap leaves one record in a bounded ring
(:func:`serve_loop_records`) that outlives the loop as the registry
does: a reader cuts any window out of a process that also warmed up,
filled and drained.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time

from pytorch_distributed_nn_tpu.obs import jitwatch, scopes
from pytorch_distributed_nn_tpu.obs import span as _span

PHASES = ("data", "compute", "collective", "checkpoint", "eval", "other")
# the serve loop's: admission's reservation pass, admissions (prefill
# and all nested in it), a round's dispatch, the wait for the round
# before it, the host's work on its tokens, the idle wait for work
SERVE_PHASES = ("next_admissions", "admit", "dispatch", "fetch",
                "round_host", "parked", "other")
SERVE_SPANS = {"next_admissions": "serve/next_admissions",
               "admit": "serve/admit", "dispatch": "serve/decode",
               "round_host": "serve/round_host", "parked": "serve/parked"}
# rounds the ring of round records holds: a 51 s window is 2,400-3,300
# rounds, a fill and a drain a few hundred more
_ROUND_RING = 16384
_rounds: collections.deque = collections.deque(maxlen=_ROUND_RING)
_loop_ids = itertools.count(1)


def serve_loop_records(t0: float = float("-inf"),
                       t1: float = float("inf")) -> list:
    """The round records of every serve loop of this process whose round
    ended in ``[t0, t1)`` on ``time.monotonic()``, oldest first (at most
    the last 16,384). A record: ``t`` the round's end, ``round``,
    ``occ``, ``loop`` (a number a meter: one serve loop's records),
    ``tid`` the loop's thread, ``wall_s`` since the record
    before it, ``busy_s`` (``wall_s`` less the idle wait for work that
    preceded the round), ``phases`` (seconds by phase, ``other``
    computed; they sum to ``wall_s``), whatever the loop handed its
    ``lap`` (the engine, in a round that admitted:
    ``first_token_wait_s``, what of the admissions was the wait for a
    prefill's first token, and ``admit_cpu_s``, what of them the thread
    spent on a core: a thread that waits, for the chip, a lock or a
    core, spends none), and only when something happened
    ``jit``
    (by phase: ``trace``, ``lower``, ``compile`` seconds of the thread
    inside that phase, the last ``fun``, ``cache_hits``,
    ``cache_misses``) and ``gc_s`` (collector seconds of the process)."""
    return [r for r in list(_rounds) if t0 <= r["t"] < t1]


@dataclasses.dataclass
class StepBreakdown:
    """One step (or one fused window) decomposed into phase seconds."""

    step: int
    wall_s: float
    phases: dict[str, float]  # measured phases + computed "other"
    accounted_frac: float  # measured (non-other) phases / wall
    names: tuple = PHASES

    def as_fields(self) -> dict:
        """Flat JSONL-able fields (the ``goodput`` event payload)."""
        out = {"step": self.step, "wall_s": round(self.wall_s, 6),
               "accounted_frac": round(self.accounted_frac, 4)}
        for name in self.names:
            out[f"{name}_s"] = round(self.phases.get(name, 0.0), 6)
        return out


class _Phase:
    """One open phase: one boundary writes the span (profiler
    annotation, recorder event) and the seconds. Unarmed, the span is
    the shared null context and is not entered."""

    __slots__ = ("_m", "_name", "_span", "_t0", "_inner", "_outer",
                 "_then")

    def __init__(self, meter: "GoodputMeter", name: str, span) -> None:
        self._m = meter
        self._name = name
        self._span = span
        self._inner = 0.0
        self._then = None

    def __enter__(self):
        m = self._m
        self._outer = m._open
        m._open = self
        if self._span is not None:
            self._span.__enter__()
        self._t0 = m._clock()
        return self

    def set(self, **args) -> None:
        """Late arguments of the phase's span."""
        if self._span is not None:
            self._span.set(**args)

    def split(self, name: str, at: float) -> int:
        """From the instant ``at`` (the meter's clock) to the phase's
        end the seconds are phase ``name``'s. Returns the microseconds
        from the phase's start to ``at``."""
        self._then = (name, at)
        return int((at - self._t0) * 1e6)

    def __exit__(self, *exc):
        m = self._m
        now = m._clock()
        if self._span is not None:
            self._span.__exit__(*exc)
        m._open = outer = self._outer
        own = now - self._t0
        if outer is not None:
            outer._inner += own
        own -= self._inner
        acc = m._step_phases
        if self._then is not None:
            name, at = self._then
            tail = min(max(now - at, 0.0), own)
            acc[name] = acc.get(name, 0.0) + tail
            own -= tail
        name = self._name
        acc[name] = acc.get(name, 0.0) + own
        jit = m._jit
        if jit is not None and jit.total != m._jit_mark[-1]:
            m._note_jit(name)
        return False


class GoodputMeter:
    """Per-step phase accumulator + running totals.

    One instance per loop, used on the loop's thread. ``phases`` names
    the loop's phases, the last of them the computed remainder;
    ``spans`` the span a phase writes (default ``goodput/<phase>``), so
    a trace capture and the breakdown describe the same intervals,
    ``cat`` their category. The trainer takes the defaults. ``clock``
    stamps everything;
    ``counter`` is a registry counter ``{phase}`` that
    :meth:`publish` brings up to date; ``rounds`` makes every
    :meth:`lap` leave a record in :func:`serve_loop_records`; ``idle``
    names the phase in which the loop waits for work, which makes no
    round a long one.
    """

    def __init__(self, phases: tuple = PHASES, spans: dict | None = None,
                 *, cat: str = "goodput", clock=time.perf_counter,
                 counter=None, rounds: bool = False, idle: str = "") -> None:
        self.phases = tuple(phases)
        self._measured = frozenset(self.phases[:-1])
        self._rest = self.phases[-1]
        self._spans = dict(spans) if spans is not None else {
            p: f"goodput/{p}" for p in self.phases[:-1]}
        self._cat = cat
        self._clock = clock
        self._counter = counter
        self._idle = idle
        self._published: dict[str, float] = {}
        self._publish_lock = threading.Lock()
        self.totals: dict[str, float] = {p: 0.0 for p in self.phases}
        self.total_wall_s = 0.0
        self.steps = 0
        self.wire_bytes_per_step: float | None = None
        self._win_totals: dict[str, float] = {p: 0.0 for p in self.phases}
        self._win_wall_s = 0.0
        self._win_steps = 0
        self._step_t0: float | None = None
        self._step_phases: dict[str, float] = {}
        self._open: _Phase | None = None
        # a loop that laps: when it started, its three longest rounds,
        # and what its thread spent in JAX's front end and the process
        # in the collector, differenced a round
        self.t_start: float | None = None
        self.longest: list = []
        self._ring = _rounds if rounds else None
        self.loop_id = next(_loop_ids)
        self._tid = 0
        self._jit: jitwatch.ThreadTotals | None = None
        self._jit_mark: tuple = ()   # ThreadTotals.mark(): ends in total
        self._jit_t = 0.0   # on time.monotonic(), as the events' ends
        self._step_jit: dict | None = None
        # phase -> () -> (fn, args[, kwargs]) of the program that phase
        # dispatches: asked only when the phase traced (obs/scopes.py)
        self.programs: dict = {}
        self._gc = None
        self._gc_seen = 0.0

    # -- per-step window -------------------------------------------------

    def step_start(self) -> None:
        self._step_t0 = self._clock()
        self._step_phases = {}

    def start(self) -> None:
        """Open the first step of a loop that laps, on the loop's
        thread (idempotent while it runs)."""
        if self._step_t0 is not None:
            return
        self.step_start()
        if self.t_start is None:
            self.t_start = self._step_t0
        self._tid = threading.get_ident()
        jitwatch.mark_loop_thread()
        self._jit = jitwatch.thread_totals()
        self._jit_mark = self._jit.mark()
        self._jit_t = time.monotonic()
        self._gc = jitwatch.gc_totals()
        self._gc_seen = self._gc.seconds

    @property
    def running(self) -> bool:
        return self._step_t0 is not None

    def phase(self, name: str, **args) -> _Phase:
        """Time one phase of the current step and write its span with
        ``args`` (unknown names raise so breakdowns stay schema-stable).
        The span says when the call inside it traced, lowered or
        compiled (:func:`jitwatch.dispatch_span`)."""
        if name not in self._measured:
            raise ValueError(f"unknown goodput phase {name!r}")
        sp = _span.span(self._spans[name], self._cat, **args)
        return _Phase(self, name, None if sp is _span._NULL
                      else jitwatch.watched(sp))

    def add_phase_seconds(self, name: str, seconds: float) -> None:
        """Attribute already-measured seconds (e.g. a trace-derived
        collective share) to the current step."""
        if name not in self._measured:
            raise ValueError(f"unknown goodput phase {name!r}")
        self._step_phases[name] = (
            self._step_phases.get(name, 0.0) + float(seconds)
        )

    def _note_jit(self, phase: str) -> None:
        """The thread's front-end totals moved: charge the difference
        to ``phase`` of the step that is open."""
        jit = self._jit
        d = jit.since(self._jit_mark)
        # of several calls that traced, name the one that took longest
        mine = [e for e in jitwatch.events(self._jit_t)
                if e.tid == self._tid]
        if mine:
            d["fun"] = max(mine, key=lambda e: e.seconds).fun
        self._jit_t = time.monotonic()
        self._jit_mark = jit.mark()
        if self._step_jit is None:
            self._step_jit = {}
        seen = self._step_jit.get(phase)
        if seen is not None:
            for k in ("trace", "lower", "compile", "cache_hits",
                      "cache_misses"):
                d[k] += seen[k]
        self._step_jit[phase] = d
        program = self.programs.get(phase)
        if program is not None:
            scopes.note(*program())

    def step_end(self, step: int = -1, *,
                 steps_covered: int = 1) -> StepBreakdown:
        """Close the window opened by :meth:`step_start`. A fused
        multistep dispatch passes ``steps_covered=k`` so throughput
        totals stay per-optimizer-step comparable."""
        if self._step_t0 is None:
            raise RuntimeError("step_end without step_start")
        wall = self._clock() - self._step_t0
        self._step_t0 = None
        phases = self._step_phases
        measured = sum(phases.values())
        # collective time is a SHARE of compute when trace-derived;
        # never let the remainder go negative from double counting
        phases[self._rest] = max(wall - measured, 0.0)
        self.steps += steps_covered
        self.total_wall_s += wall
        self._win_steps += steps_covered
        self._win_wall_s += wall
        totals, win = self.totals, self._win_totals
        for name, v in phases.items():
            totals[name] += v
            win[name] += v
        return StepBreakdown(
            step=step, wall_s=wall, phases=dict(phases),
            accounted_frac=min(measured / wall, 1.0) if wall > 0 else 0.0,
            names=self.phases,
        )

    def lap(self, step: int = -1, **fields) -> None:
        """Close the step and open the next at the same instant: the
        steps of a loop that laps partition its thread's wall time
        since :meth:`start`. Leaves one record (``fields`` with it)."""
        now = self._clock()
        wall = now - self._step_t0
        phases = self._step_phases
        self._step_t0 = now
        self._step_phases = {}
        rest = wall - sum(phases.values())
        phases[self._rest] = rest if rest > 0.0 else 0.0
        self.steps += 1
        self.total_wall_s += wall
        totals = self.totals
        for name, v in phases.items():
            totals[name] += v
        busy = wall - phases.get(self._idle, 0.0)
        rec = fields   # this call's own dict
        rec["t"] = now
        rec["round"] = step
        rec["loop"] = self.loop_id
        rec["tid"] = self._tid
        rec["wall_s"] = wall
        rec["busy_s"] = busy
        rec["phases"] = phases
        jit = self._jit
        if jit is not None and jit.total != self._jit_mark[-1]:
            self._note_jit(self._rest)
        if self._step_jit is not None:
            rec["jit"], self._step_jit = self._step_jit, None
        gc_s = self._gc.seconds
        if gc_s != self._gc_seen:
            rec["gc_s"] = gc_s - self._gc_seen
            self._gc_seen = gc_s
        top = self.longest
        if len(top) < 3 or busy > top[-1]["busy_s"]:
            top.append(rec)
            top.sort(key=lambda r: -r["busy_s"])
            del top[3:]
        if self._ring is not None:
            self._ring.append(rec)

    def stop(self) -> None:
        """End of a loop that laps: what followed the last round is a
        record of its own (``round`` -1), so the totals reach here."""
        if self._step_t0 is not None and self.t_start is not None:
            self.lap(-1)
            self._step_t0 = None

    # -- windows / summaries ---------------------------------------------

    def window_summary(self, *, reset: bool = True) -> dict:
        """Aggregate since the last window flush (the log-cadence
        ``goodput`` JSONL event payload)."""
        out = self._summarize(self._win_totals, self._win_wall_s,
                              self._win_steps)
        if reset:
            self._win_totals = {p: 0.0 for p in self.phases}
            self._win_wall_s = 0.0
            self._win_steps = 0
        return out

    def summary(self) -> dict:
        """Whole-run aggregate."""
        return self._summarize(dict(self.totals), self.total_wall_s,
                               self.steps)

    def _summarize(self, totals: dict, wall: float, steps: int) -> dict:
        out = {"steps": steps, "wall_s": round(wall, 6)}
        for name in self.phases:
            v = totals.get(name, 0.0)
            out[f"{name}_s"] = round(v, 6)
            out[f"{name}_frac"] = round(v / wall, 4) if wall > 0 else 0.0
        measured = sum(totals.get(p, 0.0) for p in self._measured)
        out["accounted_frac"] = (round(min(measured / wall, 1.0), 4)
                                 if wall > 0 else 0.0)
        if "compute" in self._measured:
            # goodput in the step-time sense: the share of wall doing
            # the actual training work (device compute incl. collectives)
            out["goodput_frac"] = (
                round((totals.get("compute", 0.0)
                       + totals.get("collective", 0.0)) / wall, 4)
                if wall > 0 else 0.0
            )
        if self.wire_bytes_per_step is not None:
            out["wire_bytes_per_step"] = round(self.wire_bytes_per_step, 1)
        return out

    def publish(self) -> None:
        """Bring the meter's registry counter up to the totals (a loop
        that laps calls this every few dozen rounds, not every round),
        and the collector's counters with it."""
        with self._publish_lock:   # the loop's thread, or a summary's
            if self._counter is not None:
                for name, v in dict(self.totals).items():
                    d = v - self._published.get(name, 0.0)
                    if d > 0:
                        self._counter.inc(d, phase=name)
                        self._published[name] = v
            jitwatch.publish()

    def report(self) -> str:
        """The line an operator reads when tokens stopped: the phase
        table and the three longest rounds with what filled them."""
        s = self.summary()
        table = " ".join(f"{p} {s[f'{p}_s']:.3f}" for p in self.phases)
        t0 = self.t_start or 0.0
        rounds = "; ".join(describe_round(r, t0) for r in self.longest)
        return (f"{s['steps']} rounds in {s['wall_s']:.3f} s, accounted "
                f"{100.0 * s['accounted_frac']:.2f} %: {table}; longest: "
                f"{rounds or 'none'}")


def describe_round(rec: dict, t_ref: float = 0.0) -> str:
    """``round 1312 at +19.9 s: 2913 ms = admit 2897 (trace 1702 lower
    1180 compile 0, _serve_prefill; cache hit 0) fetch 12 [of admit:
    first token 14.1, on a core 2880.0]``: one round record, phases
    over a tenth of a millisecond by size, then what the collector
    took, and what of the admissions was the wait for a first token and
    what the thread spent on a core."""
    jits = rec.get("jit", {})
    parts = []
    for name, v in sorted(rec["phases"].items(), key=lambda kv: -kv[1]):
        if v < 1e-4:
            continue
        part = f"{name} {v * 1e3:.1f}"
        if v > rec["busy_s"]:   # the idle wait ahead of the round
            part = f"[after {part}]"
        jit = jits.get(name)
        if jit is not None:
            part += (f" (trace {jit['trace'] * 1e3:.0f} lower "
                     f"{jit['lower'] * 1e3:.0f} compile "
                     f"{jit['compile'] * 1e3:.0f}, {jit['fun']}; cache "
                     f"hit {jit['cache_hits']} miss {jit['cache_misses']})")
        parts.append(part)
    gc_s = rec.get("gc_s")
    if gc_s:
        parts.append(f"[gc {gc_s * 1e3:.1f}]")
    if "first_token_wait_s" in rec:
        parts.append(f"[of admit: first token "
                     f"{rec['first_token_wait_s'] * 1e3:.1f}, on a core "
                     f"{rec.get('admit_cpu_s', 0.0) * 1e3:.1f}]")
    return (f"round {rec['round']} at +{rec['t'] - t_ref:.1f} s: "
            f"{rec['busy_s'] * 1e3:.1f} ms = " + " ".join(parts))
