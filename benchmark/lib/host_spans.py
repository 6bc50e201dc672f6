"""The serve loop's spans in the traced run's ``.xplane.pb``: what the
host was doing while the chip stood idle.

While a profiler session runs, the program's ``obs.span(...)`` writes a
``jax.profiler.TraceAnnotation``; it lands on the ``/host:CPU`` plane,
on the line of the thread that ran it, with its keyword arguments as
stats, on the timeline the ``/device:TPU:<n>`` planes use. The serve
loop runs on one thread (``InferenceServer._loop``), so its spans never
overlap except by nesting.

Idle time is chip 0's traced window less the union of its ``XLA Ops``
intervals, as ``device_idle_share.*`` takes it. Every idle instant is
charged to exactly one class, the first of these whose spans cover it:

- ``admit``: ``serve/admit`` and everything nested in it;
- ``retire``: ``serve/retire`` (outside an admission pass);
- ``round_return``: ``serve/decode`` or ``serve/round_host``: the
  per-token return to the host;
- ``parked``: ``serve/parked``: the loop had nothing to do;
- ``unattributed``: no serve-loop span covers it.

A program without these spans (or a trace without them) gives ``None``
everywhere: a reader that finds nothing says nothing.

    python3 benchmark/lib/host_spans.py <trace dir or .xplane.pb>

prints the classes, the second level and the clock check of one trace.
"""

from __future__ import annotations

import functools
import statistics
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib import trace_reduce as tr
from benchmark.lib.common import log

ROOT = Path(__file__).resolve().parents[2]


def load_spans(path: str) -> list:
    """``[(name, start_ns, end_ns, stats)]`` of the serve-loop thread:
    the ``/host:CPU`` line that holds the most ``serve/*`` events, by
    start. Empty when no line holds one."""
    from jax.profiler import ProfileData

    best: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns),
                    float(e.start_ns + e.duration_ns), dict(e.stats))
                   for e in line.events if e.name.startswith("serve/")]
            if len(evs) > len(best):
                best = evs
    return sorted(best, key=lambda ev: (ev[1], -ev[2]))


def intersect(a: list, b: list) -> list:
    """The part of the disjoint sorted intervals ``a`` that the
    disjoint sorted intervals ``b`` cover."""
    return tr.subtract(a, tr.subtract(a, b))


def _cover(spans: list, *names: str) -> list:
    return tr.union((s, e) for n, s, e, _ in spans if n in names)


def class_cover(spans: list) -> dict:
    """``{class: disjoint intervals}`` for the four classes a span
    covers, each less the classes before it."""
    admit = _cover(spans, "serve/admit")
    retire = tr.subtract(_cover(spans, "serve/retire"), admit)
    taken = tr.union(admit + retire)
    rr = tr.subtract(_cover(spans, "serve/decode", "serve/round_host"),
                     taken)
    taken = tr.union(taken + rr)
    parked = tr.subtract(_cover(spans, "serve/parked"), taken)
    return dict(admit=admit, retire=retire, round_return=rr, parked=parked)


def charge(idle: list, spans: list) -> dict:
    """Nanoseconds of the disjoint sorted ``idle`` intervals by class;
    the five sum to ``total(idle)``."""
    out = {k: tr.total(intersect(idle, cover))
           for k, cover in class_cover(spans).items()}
    out["unattributed"] = tr.total(idle) - sum(out.values())
    return out


def device_window(devs: dict) -> tuple:
    """(start, end) of the traced window as ``run.py`` takes it: from
    the first to the last program or operation of any chip."""
    span = [(s, e) for d in devs.values() for k in ("modules", "ops")
            for _, s, e in d[k]]
    return min(s for s, _ in span), max(e for _, e in span)


def idle_intervals(devs: dict) -> list:
    """Chip 0's window less the union of its ``XLA Ops`` intervals."""
    w0, w1 = device_window(devs)
    d0 = devs[min(devs)]
    return tr.subtract([(w0, w1)], tr.union((s, e) for _, s, e in d0["ops"]))


def unattributed_parts(idle: list, spans: list) -> dict:
    """Where the idle time that no class covers lies, in nanoseconds:
    ``off_host`` before the first and after the last span (the host's
    tracer starts and stops apart from the device's), ``before_admit``
    inside a ``serve/round`` ahead of its ``serve/admit`` (the span
    opens when ``next_admissions`` has returned), ``in_round`` elsewhere
    inside a round, ``between_rounds`` the rest."""
    cover = class_cover(spans)
    left = tr.subtract(idle, tr.union(
        [iv for c in cover.values() for iv in c]))
    if not spans:
        return dict(off_host=tr.total(left), before_admit=0.0,
                    in_round=0.0, between_rounds=0.0)
    h0, h1 = spans[0][1], max(e for _, _, e, _ in spans)
    on_host = intersect(left, [(h0, h1)])
    rounds = [(s, e) for n, s, e, _ in spans if n == "serve/round"]
    admits = sorted(s for n, s, _, _ in spans if n == "serve/admit")
    ahead = []
    for s, e in rounds:
        first = next((a for a in admits if s <= a < e), None)
        if first is not None:
            ahead.append((s, first))
    in_round = intersect(on_host, tr.union(rounds))
    before = tr.total(intersect(in_round, tr.union(ahead)))
    return dict(off_host=tr.total(left) - tr.total(on_host),
                before_admit=before,
                in_round=tr.total(in_round) - before,
                between_rounds=tr.total(on_host) - tr.total(in_round))


def second_level(idle: list, spans: list, w0: float) -> dict:
    """``{span name: (count, span seconds, idle seconds under it,
    longest in ms, the longest one's offset in the window in s)}``."""
    out = {}
    for name in sorted({n for n, *_ in spans}):
        mine = [(s, e) for n, s, e, _ in spans if n == name]
        longest = max(mine, key=lambda se: se[1] - se[0])
        out[name] = (len(mine), tr.total(mine) / 1e9,
                     tr.total(intersect(idle, tr.union(mine))) / 1e9,
                     (longest[1] - longest[0]) / 1e6,
                     (longest[0] - w0) / 1e9)
    return out


def clock_check(spans: list, devs: dict,
                program: str = "jit__serve_step") -> dict | None:
    """Are host and device events on one timeline? Each execution of
    the decode program on chip 0 against the ``serve/decode`` span that
    dispatched it and fetched its result (the span that holds the
    execution's midpoint, else the nearest): the share that start after
    the span starts and end before it ends, and the median slack at
    both ends in microseconds (negative: the execution sticks out)."""
    decode = [(s, e) for n, s, e, _ in spans if n == "serve/decode"]
    execs = [(s, e) for n, s, e in devs[min(devs)]["modules"]
             if n == program]
    if not decode:
        return None
    # an execution whose span began before the session did has no span
    execs = [(s, e) for s, e in execs
             if decode[0][0] <= 0.5 * (s + e) <= decode[-1][1]]
    if not execs:
        return None
    head, tail = [], []
    for s, e in execs:
        mid = 0.5 * (s + e)
        ds, de = min(decode, key=lambda d: max(d[0] - mid, mid - d[1], 0.0))
        head.append((s - ds) / 1e3)
        tail.append((de - e) / 1e3)
    nested = sum(1 for h, t in zip(head, tail) if h >= 0.0 and t >= 0.0)
    return dict(executions=len(execs), nested=nested,
                head_slack_p50_us=statistics.median(head),
                tail_slack_p50_us=statistics.median(tail),
                head_slack_min_us=min(head), tail_slack_min_us=min(tail))


@functools.lru_cache(maxsize=2)
def analyze(path: str) -> dict | None:
    """Everything the readers take from one trace file, computed and
    logged once. ``None`` when the file holds no serve-loop span."""
    spans = load_spans(path)
    if not spans:
        return None
    devs = tr.load(path)
    if not devs:
        return None
    w0, w1 = device_window(devs)
    idle = idle_intervals(devs)
    by_class = charge(idle, spans)
    out = dict(window_ns=w1 - w0, idle_ns=tr.total(idle), spans=spans,
               by_class=by_class, second=second_level(idle, spans, w0),
               unattributed=unattributed_parts(idle, spans),
               clock=clock_check(spans, devs))
    window_s = out["window_ns"] / 1e9
    log("idle by class (% of the traced window): " + ", ".join(
        f"{k} {100.0 * v / out['window_ns']:.2f}"
        for k, v in by_class.items())
        + f"; idle {100.0 * out['idle_ns'] / out['window_ns']:.2f} of "
        f"{window_s:.3f} s")
    log("spans (count, span s, idle s under it, longest ms at s): "
        + "; ".join(f"{n} {c} {ss:.3f} {si:.3f} {lm:.1f}@{at:.2f}"
                    for n, (c, ss, si, lm, at) in out["second"].items()))
    log("unattributed (ms): " + ", ".join(
        f"{k} {v / 1e6:.1f}" for k, v in out["unattributed"].items()))
    if out["clock"] is not None:
        c = out["clock"]
        log(f"clock check: {c['nested']} of {c['executions']} decode "
            f"executions inside their serve/decode span; median slack "
            f"{c['head_slack_p50_us']:.0f} us at the start, "
            f"{c['tail_slack_p50_us']:.0f} us at the end (least "
            f"{c['head_slack_min_us']:.0f}, {c['tail_slack_min_us']:.0f})")
    return out


def of_run(run: dict) -> dict | None:
    """The analysis of a traced run's file under
    ``.bench_trace/<cell>/``, or None (untraced, or no span in it)."""
    if run.get("trace") is None:
        return None
    try:
        path = tr.find_xplane(str(ROOT / ".bench_trace" / run["workload"]))
    except FileNotFoundError:
        return None
    return analyze(path)


def idle_share_pct(run: dict, cls: str, also: tuple = ()):
    """Idle time charged to ``cls`` (plus the classes in ``also``, for a
    cell that does not report them apart), in % of the traced window."""
    a = of_run(run)
    if a is None:
        return None
    return 100.0 * sum(a["by_class"][k] for k in (cls, *also)) \
        / a["window_ns"]


def span_p50_ms(run: dict, name: str):
    """Median length of the spans called ``name``, in milliseconds."""
    a = of_run(run)
    if a is None:
        return None
    lens = [(e - s) / 1e6 for n, s, e, _ in a["spans"] if n == name]
    return statistics.median(lens) if lens else None


def prefill_pad_share_pct(run: dict):
    """1 - sum(tokens) / sum(padded) over the ``serve/prefill_into``
    spans: prompt positions computed for padding."""
    a = of_run(run)
    if a is None:
        return None
    into = [st for n, _, _, st in a["spans"] if n == "serve/prefill_into"]
    padded = sum(int(st["padded"]) for st in into)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(int(st["tokens"]) for st in into) / padded)


def main(argv: list) -> int:
    path = argv[0]
    if not path.endswith(".pb"):
        path = tr.find_xplane(path)
    if analyze(path) is None:
        print(f"no serve-loop span in {path}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
