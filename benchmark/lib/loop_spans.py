"""The serve loop's newer spans in the traced run (PR 37), beside
``host_spans.py``, whose classes and intervals they build on.

``serve/next_admissions`` wraps the scheduler's admission pass (prefix
match, reservation, the shedding of cached blocks), which runs before
``serve/admit`` opens: idle time under it was ``unattributed`` to the
four classes of ``host_spans.py``. Here it is taken out of what those
classes leave, so that ``idle_next_admissions_share`` and the remainder
this module logs add up to the cell's ``idle_unattributed_share`` of
the same run.

``serve/decode`` carries ``dispatch_us`` (from the span's start to the
stamp the loop takes before it waits for the chip), which gives a clock
check that means something with a round in flight: the decode program
a span dispatched is queued behind the one still running, so it starts
after the span's start and within a round of the stamp (LongCat,
PR 37: 130 of 133, 13.6 ms after it at the median; the other three
stood behind a retire's ``_save_blocks``).

A trace without these spans (a parent commit's) gives ``None``.
"""

from __future__ import annotations

import functools
import statistics

from benchmark.lib import host_spans
from benchmark.lib import trace_reduce as tr
from benchmark.lib.common import log

SPAN = "serve/next_admissions"


def split_unattributed(idle: list, spans: list) -> dict:
    """Nanoseconds of the idle intervals that none of the four classes
    covers, by whether a ``serve/next_admissions`` span does:
    ``next_admissions`` and ``rest`` sum to ``charge()``'s
    ``unattributed``; ``rest_parts`` says where the rest lies (as
    ``host_spans.unattributed_parts`` does, less this span)."""
    cover = host_spans.class_cover(spans)
    left = tr.subtract(idle, tr.union(
        [iv for c in cover.values() for iv in c]))
    mine = tr.union((s, e) for n, s, e, _ in spans if n == SPAN)
    under = host_spans.intersect(left, mine)
    rest = tr.subtract(left, mine)
    return dict(next_admissions=tr.total(under),
                rest=tr.total(left) - tr.total(under),
                rest_parts=host_spans.unattributed_parts(
                    rest, [sp for sp in spans if sp[0] != SPAN])
                if spans else {})


# An execution dispatched onto an idle chip (the first after an
# admission) appears in a trace 0.1-0.5 ms *before* its span opens: the
# device's clock runs that far ahead of the host's (LongCat, PR 37: 17
# of 133). In steady state the execution that is running when a span
# opens began 2.3-4.4 ms before it, and is the span's before.
CLOCK_SLACK_NS = 1.5e6


def dispatch_check(spans: list, devs: dict,
                   program: str = "jit__serve_step") -> dict | None:
    """Each ``serve/decode`` span that carries ``dispatch_us`` against
    the execution of the decode program on chip 0 that it dispatched:
    the first that no earlier span took and that starts no more than
    ``CLOCK_SLACK_NS`` before the span does (in order, not by
    midpoint). How many start within one round (the median execution)
    after the stamp, or between the span's start and the stamp (the
    chip was idle); the median and the extreme slack from the stamp to
    the execution's start, in microseconds. An execution that starts
    later stood behind another program (a retire's ``_save_blocks``)."""
    decode = [(s, s + 1e3 * float(st["dispatch_us"]))
              for n, s, _, st in spans
              if n == "serve/decode" and "dispatch_us" in st]
    execs = sorted((s, e) for n, s, e in devs[min(devs)]["modules"]
                   if n == program)
    if not decode or not execs:
        return None
    round_ns = statistics.median(e - s for s, e in execs)
    slack, within, k = [], 0, 0
    for start, stamp in decode:
        while k < len(execs) and execs[k][0] < start - CLOCK_SLACK_NS:
            k += 1
        if k == len(execs):
            break
        at = execs[k][0]
        slack.append((at - stamp) / 1e3)
        within += start - CLOCK_SLACK_NS <= at <= stamp + round_ns
        k += 1
    if not slack:
        return None
    return dict(spans=len(decode), matched=len(slack),
                within_a_round=within, round_us=round_ns / 1e3,
                slack_p50_us=statistics.median(slack),
                slack_min_us=min(slack), slack_max_us=max(slack))


@functools.lru_cache(maxsize=2)
def analyze(path: str) -> dict | None:
    """What the readers take from one trace file, computed and logged
    once. ``None`` when it holds no ``serve/next_admissions`` span."""
    base = host_spans.analyze(path)
    if base is None or not any(n == SPAN for n, *_ in base["spans"]):
        return None
    spans = base["spans"]
    devs = tr.load(path)
    idle = host_spans.idle_intervals(devs)
    out = split_unattributed(idle, spans)
    out["window_ns"] = base["window_ns"]
    out["by_class"] = base["by_class"]
    out["clock"] = dispatch_check(spans, devs)
    w = out["window_ns"]
    also = sum(base["by_class"][k] for k in ("retire", "parked"))
    log(f"unattributed idle, split (% of the traced window): under "
        f"{SPAN} {100.0 * out['next_admissions'] / w:.3f}, remainder "
        f"{100.0 * out['rest'] / w:.3f} (with the retire and parked "
        f"classes, as the closed-loop cells report it: "
        f"{100.0 * (out['rest'] + also) / w:.3f}); the remainder in ms: "
        + ", ".join(f"{k} {v / 1e6:.1f}"
                    for k, v in out["rest_parts"].items()))
    for name in (SPAN, "serve/prefix_match", "serve/evict",
                 "serve/release"):
        mine = [(s, e, st) for n, s, e, st in spans if n == name]
        if mine:
            log(f"{name}: {len(mine)} spans, "
                f"{sum(e - s for s, e, _ in mine) / 1e6:.1f} ms, longest "
                f"{max(e - s for s, e, _ in mine) / 1e6:.2f} ms, blocks "
                f"{sum(int(st.get('blocks', 0)) for _, _, st in mine)}")
    c = out["clock"]
    if c is not None:
        log(f"clock check by dispatch stamp: {c['within_a_round']} of "
            f"{c['matched']} decode executions start within a round "
            f"({c['round_us']:.0f} us) after their span's dispatch_us "
            f"stamp ({c['spans']} spans); slack median "
            f"{c['slack_p50_us']:.0f} us, least {c['slack_min_us']:.0f}, "
            f"most {c['slack_max_us']:.0f}")
    return out


def of_run(run: dict) -> dict | None:
    if run.get("trace") is None:
        return None
    try:
        path = tr.find_xplane(
            str(host_spans.ROOT / ".bench_trace" / run["workload"]))
    except FileNotFoundError:
        return None
    return analyze(path)


def next_admissions_share_pct(run: dict):
    """Idle time of chip 0 under ``serve/next_admissions`` and under
    none of the four classes, in % of the traced window."""
    a = of_run(run)
    if a is None:
        return None
    return 100.0 * a["next_admissions"] / a["window_ns"]
