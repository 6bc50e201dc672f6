"""The readers of the serve loop's own account (PR 37): on a whole run
of a tiny cell on the CPU, on run dictionaries that hold nothing, on a
synthetic plane, and on the recorded trace of a program that has no
such span.

A CPU run gives the readers something to read, never a device number:
what is asserted is that the phases cover the window and each reader
returns a number.
"""

from pathlib import Path

import pytest

from benchmark import run as bench_run
from benchmark.lib import host_spans as hs
from benchmark.lib import loop_records, loop_spans
from pytorch_distributed_nn_tpu import obs

DATA = Path(__file__).parent / "data"

RECORD_METRICS = (
    "loop_fetch_wait_share", "loop_next_admissions_share",
    "loop_admit_share", "loop_unaccounted_share", "loop_longest_round_ms",
    "window_gc_pause_ms", "window_jit_seconds")


def _serve(traffic: str, seconds: float, **kw):
    return bench_run.run_cell(
        workload=f"tiny_{traffic}", config_file=DATA / "tiny_decoder.json",
        traffic_file=DATA / f"tiny_{traffic}.json",
        cell_file=DATA / "cells" / "tiny_serve.json", chips=1,
        seed=2**31 + 37, seconds=seconds, traced=False, check_device=False,
        **kw)


def _read(run, names):
    return {k: v["value"] for k, v in bench_run.read_metrics(
        [dict(name=n, unit="x") for n in names], run).items()}


@pytest.mark.parametrize("traffic, suffix",
                         [("docs", ""), ("chat", ".chat")])
def test_every_record_metric_reads_a_number_from_an_untraced_run(
        traffic, suffix):
    run = _serve(traffic, 2.0)
    assert run["correct"], run["check"]
    names = [n if n == "window_jit_seconds" else n + suffix
             for n in RECORD_METRICS]
    m = _read(run, names)
    assert set(m) == set(names)
    shares = [m[f"loop_{p}_share{suffix}"] for p in
              ("fetch_wait", "next_admissions", "admit", "unaccounted")]
    assert all(0.0 <= s <= 100.0 for s in shares)
    # the phases of the window sum to the window: the records cover it
    a = loop_records.account(run)
    assert a["covered_s"] == pytest.approx(a["window_s"], rel=0.01)
    assert sum(a["by_phase"].values()) == pytest.approx(a["window_s"],
                                                        rel=0.01)
    assert a["rounds"] > 10
    assert 0.0 < m["loop_longest_round_ms" + suffix] <= 2000.0
    assert m["window_gc_pause_ms" + suffix] >= 0.0
    # warm-up covered every shape: nothing traced inside the window
    assert m["window_jit_seconds"] == 0.0
    # the traced-only reader says nothing in an untraced run
    assert _read(run, ["idle_next_admissions_share"]) == {}


def test_a_training_run_reads_window_jit_seconds_and_no_round_record():
    run = bench_run.run_cell(
        workload="tiny_train", config_file=DATA / "tiny_encoder.json",
        traffic_file=DATA / "tiny_mlm.json",
        cell_file=DATA / "cells" / "tiny_train.json", chips=1, seed=5,
        seconds=1.0, traced=False, check_device=False)
    m = _read(run, ["window_jit_seconds"])
    assert m == {"window_jit_seconds": 0.0}
    # the thread that called trainer.train is a loop's thread
    import threading
    assert threading.get_ident() in obs.jitwatch.loop_threads()


def test_a_retrace_inside_the_window_reads_over_zero_with_its_function():
    """What the stall's candidate would read: a jitted call on a loop's
    thread that finds no executable inside ``[t0, t1)``."""
    import time

    import jax
    import jax.numpy as jnp

    obs.jitwatch.install()
    obs.jitwatch.mark_loop_thread()

    @jax.jit
    def _window_probe(x):
        return x * 5 - 2

    _window_probe(jnp.arange(3.0)).block_until_ready()
    t0 = time.monotonic()
    jax.clear_caches()
    _window_probe(jnp.arange(3.0)).block_until_ready()
    t1 = time.monotonic()
    got = loop_records.window_jit_seconds(dict(t0=t0, t1=t1))
    assert 0.0 < got <= t1 - t0
    evs = [e for e in obs.jitwatch.events(t0, t1)
           if e.fun == "_window_probe"]
    assert {e.stage for e in evs} == {"trace", "lower", "compile"}
    assert loop_records.window_jit_seconds(dict(t0=t1, t1=t1 + 1.0)) == 0.0


def test_readers_say_nothing_without_records_or_spans():
    for run in (dict(), dict(t0=None, t1=None),
                # a window in which no round ended
                dict(t0=1.0, t1=2.0, trace=None, workload="x")):
        for name in RECORD_METRICS:
            if name == "window_jit_seconds":
                continue
            assert _read(run, [name, name + ".chat"]) == {}, (run, name)
    assert _read(dict(), ["window_jit_seconds"]) == {}
    assert _read(dict(trace=None, workload="x"),
                 ["idle_next_admissions_share"]) == {}
    assert _read(dict(trace=dict(window_s=1.0), workload="no_such_cell"),
                 ["idle_next_admissions_share"]) == {}
    # traces of programs that have no such span: the parent commit's
    # (no span at all) and PR 27's (the serve loop's first eleven)
    assert loop_spans.analyze(str(DATA / "recorded.xplane.pb")) is None
    assert loop_spans.analyze(str(DATA / "recorded_spans.xplane.pb")) is None


def test_the_recorded_trace_of_the_present_spans_splits_its_idle_time():
    """``recorded_loop_spans.xplane.pb``: ``record_spans.py`` on the chip
    at PR 37 (a two-layer decoder of width 64, three requests), the
    first recording that holds ``serve/next_admissions``, its nested
    spans, ``serve/release`` and ``dispatch_us``."""
    path = str(DATA / "recorded_loop_spans.xplane.pb")
    a = loop_spans.analyze(path)
    base = hs.analyze(path)
    assert a is not None and a["window_ns"] == base["window_ns"]
    assert a["next_admissions"] > 0 and a["rest"] > 0
    assert a["next_admissions"] + a["rest"] == pytest.approx(
        base["by_class"]["unattributed"], abs=1.0)   # nanoseconds
    assert sum(a["rest_parts"].values()) == pytest.approx(a["rest"],
                                                          abs=1.0)
    names = {n for n, *_ in base["spans"]}
    assert {"serve/next_admissions", "serve/prefix_match",
            "serve/release"} <= names
    decode = [st for n, _, _, st in base["spans"] if n == "serve/decode"]
    assert decode and all(st["dispatch_us"] >= 0 for st in decode)
    c = a["clock"]
    assert c["spans"] == len(decode) and 0 < c["matched"] <= c["spans"]
    # through the metric's own file: a traced run of that cell's name
    # finds no trace directory here and says nothing
    assert _read(dict(trace=dict(window_s=1.0), workload="tiny_docs"),
                 ["idle_next_admissions_share"]) == {}


def test_edge_rounds_count_by_the_part_inside_the_window():
    recs = [dict(t=10.5, wall_s=1.0, busy_s=1.0, loop=1, tid=1, round=1,
                 phases=dict(fetch=0.8, admit=0.2)),
            dict(t=11.5, wall_s=1.0, busy_s=0.5, loop=1, tid=1, round=2,
                 gc_s=0.25, phases=dict(fetch=0.5, parked=0.5)),
            dict(t=12.5, wall_s=1.0, busy_s=0.0, loop=1, tid=1, round=3,
                 phases=dict(parked=1.0))]
    a = loop_records._reduce(recs, 10.0, 12.0)
    assert a["covered_s"] == pytest.approx(2.0)
    assert a["by_phase"]["fetch"] == pytest.approx(0.4 + 0.5)
    assert a["by_phase"]["admit"] == pytest.approx(0.1)
    assert a["by_phase"]["parked"] == pytest.approx(0.5 + 0.5)
    assert sum(a["by_phase"].values()) == pytest.approx(a["window_s"])
    # the longest round is the busiest, not the one that waited longest
    assert a["rounds"] == 2 and a["longest"][0]["round"] == 1
    assert a["gc_s"] == 0.25


# -- the synthetic plane ------------------------------------------------------

def _sp(name, s, e, **stats):
    return (name, float(s), float(e), stats)


# a round whose admission pass found work (next_admissions 2-10 with a
# prefix match and an eviction inside it, admit 10-50), a plain round
# with a pass that found none (112-114), and bare time between them
SPANS = [
    _sp("serve/round", 0, 100, round=1, occ=2),
    _sp("serve/next_admissions", 2, 10, queued=3, admitted=1,
        lock_wait_us=4),
    _sp("serve/prefix_match", 3, 5, blocks=2),
    _sp("serve/evict", 5, 9, blocks=7),
    _sp("serve/admit", 10, 50, n=1),
    _sp("serve/decode", 52, 80, dispatch_us=0.004),
    _sp("serve/round_host", 80, 98, retired=1),
    _sp("serve/retire", 90, 96, n=1),
    _sp("serve/release", 91, 95, blocks=3),
    _sp("serve/round", 110, 150, round=2, occ=1),
    _sp("serve/next_admissions", 112, 114, queued=0, admitted=0,
        lock_wait_us=1),
    _sp("serve/decode", 115, 140, dispatch_us=0.003),
    _sp("serve/round_host", 140, 149, retired=0),
]
IDLE = [(0, 30),      # 2 bare, 8 under next_admissions, 20 of the admission
        (98, 113),    # 2 bare in round 1, 10 between rounds, 2 bare, 1 under
        (150, 170)]   # after the last span


def test_next_admissions_and_the_remainder_add_up_to_unattributed():
    by_class = hs.charge(IDLE, SPANS)
    got = loop_spans.split_unattributed(IDLE, SPANS)
    assert got["next_admissions"] == 8.0 + 1.0
    assert got["next_admissions"] + got["rest"] == by_class["unattributed"]
    # to 0.01 point of any window: here exactly
    window = 170.0
    share = 100.0 * got["next_admissions"] / window
    rest = 100.0 * got["rest"] / window
    assert share + rest == pytest.approx(
        100.0 * by_class["unattributed"] / window, abs=0.01)
    # what is left: 2 ahead of the admission pass, 2 + 2 elsewhere in a
    # round, 10 between rounds, 20 after the host's last span
    parts = got["rest_parts"]
    assert parts["between_rounds"] == 10.0 and parts["off_host"] == 20.0
    assert parts["before_admit"] + parts["in_round"] == 6.0
    assert sum(parts.values()) == got["rest"]


def test_a_plane_without_the_span_leaves_unattributed_whole():
    old = [sp for sp in SPANS if sp[0] not in (
        "serve/next_admissions", "serve/prefix_match", "serve/evict",
        "serve/release")]
    got = loop_spans.split_unattributed(IDLE, old)
    assert got["next_admissions"] == 0.0
    assert got["rest"] == hs.charge(IDLE, old)["unattributed"]


def test_dispatch_check_matches_in_order_by_the_stamp():
    # milliseconds as nanoseconds. Round in flight: the program a span
    # dispatched starts when the one before it ends, a little after the
    # stamp (start + dispatch_us); the first after an admission starts
    # on an idle chip and reads 0.2 ms before its span opens
    ms = 1e6
    spans = [_sp("serve/decode", 52 * ms, 80 * ms, dispatch_us=4000),
             _sp("serve/decode", 115 * ms, 140 * ms, dispatch_us=3000),
             _sp("serve/decode", 300 * ms, 302 * ms, dispatch_us=900),
             _sp("serve/decode", 303 * ms, 320 * ms, dispatch_us=1000)]
    devs = {0: dict(modules=[("jit__serve_step", 40 * ms, 60 * ms),
                             ("jit__serve_step", 60 * ms, 100 * ms),
                             ("jit__serve_step", 118 * ms, 150 * ms),
                             ("jit__other", 61 * ms, 62 * ms),
                             ("jit__serve_step", 299.8 * ms, 319 * ms),
                             ("jit__save_blocks", 319 * ms, 340 * ms),
                             ("jit__serve_step", 340 * ms, 360 * ms)],
                    ops=[], **{"async": []})}
    c = loop_spans.dispatch_check(spans, devs)
    # span 52-80 (stamp 56) takes the execution at 60 (the one at 40
    # began 12 ms before it: the round before), span 115-140 (stamp 118)
    # the one at 118, the span after the admission the one at 299.8,
    # and the last the one at 340, which stood behind a save: 36 ms
    # after the stamp where a round (the median execution) is 20
    assert c["spans"] == 4 and c["matched"] == 4
    assert c["round_us"] == pytest.approx(20e3)
    assert c["slack_min_us"] == pytest.approx(-1100.0)
    assert c["slack_max_us"] == pytest.approx(36e3)
    assert c["slack_p50_us"] == pytest.approx((0.0 + 4000.0) / 2)
    assert c["within_a_round"] == 3
    assert loop_spans.dispatch_check(
        [sp for sp in SPANS if sp[0] != "serve/decode"], devs) is None
    assert loop_spans.dispatch_check(SPANS, {0: dict(modules=[])}) is None


def test_dispatch_check_on_the_longcat_cells_traced_run():
    """``timeline_longcat_pr37.json.gz``: the serve loop's spans and
    chip 0's module executions of the traced LongCat run of PR 37 (my
    chip run; 4 s, 133 decode spans, 17 admissions)."""
    import gzip
    import json

    with gzip.open(DATA / "timeline_longcat_pr37.json.gz", "rt") as f:
        d = json.load(f)
    spans = [(n, s, e, st) for n, s, e, st in d["spans"]]
    devs = {0: dict(modules=[tuple(m) for m in d["modules"]])}
    c = loop_spans.dispatch_check(spans, devs)
    assert c["spans"] == c["matched"] == 133
    assert c["within_a_round"] == 130
    assert 13e3 < c["slack_p50_us"] < 14e3      # a round less ~5.7 ms
    assert -1500.0 < c["slack_min_us"] < 0.0    # the chip was idle
    assert c["round_us"] < c["slack_max_us"] < 25e3   # behind a save
