"""The serve loop's account of its own thread (ISSUE 37): the phases
partition the thread's wall time, every round leaves a record, a call
that traced is named in the record, the registry and (armed) the span,
and one listener a process hears JAX and the collector.

CPU, the tiny llama of conftest.py. The profiler session is the one
``tests/test_serve_spans.py`` uses.
"""

import collections
import gc
import threading
import time

import jax
import jax.monitoring
import numpy as np
import pytest
from test_serve_spans import _line_with, _Session

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.obs import flight, goodput, jitwatch
from pytorch_distributed_nn_tpu.obs.goodput import PHASES, GoodputMeter
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import InferenceServer, ServingEngine

VOCAB = 97
MEASURED = goodput.SERVE_PHASES[:-1]


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    chaos.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    if jitwatch.installed():
        # the listener's function names are the registry's labels and go
        # with them: a worker that ran other files first may have used
        # up all MAX_FUNS, and ``_serve_prefill`` would read "other"
        jitwatch._watch.funs.clear()
    obs.disable_tracing()
    yield
    chaos.reset()
    obs.disable_tracing()


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, VOCAB, size=(n,)).astype(np.int32)


def _engine(tiny_llama, **kw):
    model, params = tiny_llama
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("block_size", 16)
    return ServingEngine(model, params, **kw)


def _serve(server, lens_and_budgets, seed=0):
    reqs = [server.submit(_prompt(n, seed + i), k)
            for i, (n, k) in enumerate(lens_and_budgets)]
    for r in reqs:
        assert r.done.wait(120.0) and r.state == "done"
    return reqs


def _mine(eng, t0=float("-inf"), t1=float("inf")):
    """The round records of one engine's loop."""
    return [r for r in obs.serve_loop_records(t0, t1)
            if r["loop"] == eng.loop.loop_id]


# -- the partition ----------------------------------------------------------

def test_phases_of_a_served_batch_partition_the_loop_threads_wall(
        tiny_llama):
    eng = _engine(tiny_llama)
    before = time.monotonic()
    server = InferenceServer(eng, idle_wait_s=0.002).start()
    _serve(server, [(5, 6), (19, 4), (7, 9)])
    # parked: as a server mostly is. On the CPU a warm round of the tiny
    # model is 0.3 ms, a tenth of it the loop's own Python between the
    # phases (on the chip a round is 15-21 ms and that share 0.2 %)
    time.sleep(0.25)
    _serve(server, [(30, 5)], seed=7)
    server.stop(timeout=60.0)
    after = time.monotonic()
    s = eng.loop.summary()
    by_phase = sum(s[f"{p}_s"] for p in goodput.SERVE_PHASES)
    assert by_phase == pytest.approx(s["wall_s"], rel=1e-4)
    # the thread ran from a moment after start() to a moment before
    # stop() returned
    assert s["wall_s"] <= after - before
    assert s["wall_s"] == pytest.approx(after - before, rel=0.01, abs=0.01)
    assert s["accounted_frac"] > 0.98
    assert s["parked_s"] > 0.2 and s["admit_s"] > 0 and s["fetch_s"] > 0
    assert "goodput_frac" not in s   # the trainer's notion, not a loop's
    # the records partition the same time: each round's phases sum to
    # its wall, the walls to the thread's, and the ends are the stamps
    recs = _mine(eng)
    assert sum(r["wall_s"] for r in recs) == pytest.approx(s["wall_s"],
                                                           rel=1e-6)
    for r in recs:
        assert sum(r["phases"].values()) == pytest.approx(r["wall_s"],
                                                          rel=1e-6, abs=1e-9)
        # a round is as long as its wall less the idle wait ahead of it
        assert r["busy_s"] == pytest.approx(
            r["wall_s"] - r["phases"].get("parked", 0.0))
        assert set(r["phases"]) <= set(goodput.SERVE_PHASES)
        # of a round that admitted: the wait for the first tokens, and
        # the thread's seconds on a core (another clock, coarser)
        if "first_token_wait_s" in r:
            assert 0.0 < r["first_token_wait_s"] <= r["phases"]["admit"]
            assert 0.0 <= r["admit_cpu_s"] <= r["phases"]["admit"] + 0.02
    admitted = [r for r in recs if r["phases"].get("admit", 0.0) > 0.0]
    assert admitted and all("first_token_wait_s" in r for r in admitted)
    assert not [r for r in recs if "first_token_wait_s" in r
                and r not in admitted]
    ends = [r["t"] for r in recs]
    assert ends == sorted(ends) and before < ends[0] and ends[-1] < after
    assert [r["round"] for r in recs[:-1]] == sorted(
        r["round"] for r in recs[:-1])
    assert recs[-1]["round"] == -1   # what followed the last round
    assert all(0 <= r["occ"] <= 4 for r in recs[:-1])
    # published by phase, and in the engine's summary with the rounds
    snap = obs.get_registry().snapshot()
    for p in ("admit", "fetch", "parked"):
        assert snap[f'serve_loop_seconds_total{{phase="{p}"}}'] == \
            pytest.approx(s[f"{p}_s"], rel=1e-3)
    summ = eng.summary()
    assert summ["loop"]["steps"] == len(recs)
    assert len(summ["longest_rounds"]) == 3
    assert all(line.startswith("round ") for line in summ["longest_rounds"])
    assert any("[of admit: first token " in line
               for line in summ["longest_rounds"])


def test_stop_logs_the_phase_table_and_the_longest_rounds(tiny_llama, caplog):
    server = InferenceServer(_engine(tiny_llama)).start()
    _serve(server, [(5, 3), (19, 2)])
    with caplog.at_level("INFO",
                         logger="pytorch_distributed_nn_tpu.serve.server"):
        server.stop(timeout=60.0)
    [line] = [r.getMessage() for r in caplog.records
              if r.getMessage().startswith("serve loop:")]
    for p in goodput.SERVE_PHASES:
        assert f" {p} " in line
    assert "accounted" in line and line.count("round ") == 3
    assert " ms = " in line


def test_an_engine_stepped_without_a_server_keeps_the_same_account(
        tiny_llama):
    eng = _engine(tiny_llama)
    reqs = [eng.submit(_prompt(5), 4), eng.submit(_prompt(19), 3)]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    s = eng.loop.summary()
    assert s["steps"] == len(eng.round_seconds) == len(_mine(eng))
    # warm rounds of the tiny model take ~0.3 ms on the CPU, of which the
    # loop's own Python between the phases is 20-25 us (0.92 alone on
    # this machine, less beside other workers)
    assert s["accounted_frac"] > 0.8 and s["parked_s"] == 0.0


# -- a call that traced -------------------------------------------------------

def test_a_forced_retrace_is_named_in_record_registry_and_span(
        tiny_llama, tmp_path):
    eng = _engine(tiny_llama)
    server = InferenceServer(eng).start()
    try:
        _serve(server, [(5, 3)])       # warms the 16-token bucket
        with _Session(tmp_path) as sess:
            t0 = time.monotonic()
            _serve(server, [(6, 3)], seed=3)    # the same bucket: warm
            t1 = time.monotonic()
            # every executable gone: the next prefill of the warm bucket
            # traces, lowers and compiles again, as a call that finds no
            # executable for its shapes does
            jax.clear_caches()
            _serve(server, [(7, 3)], seed=5)
            t2 = time.monotonic()
    finally:
        server.stop(timeout=60.0)
    warm, cold = _mine(eng, t0, t1), _mine(eng, t1, t2)
    assert warm and not any("jit" in r for r in warm)
    noted = [r for r in cold if "admit" in r.get("jit", {})]
    assert noted, "no round record names the retrace"
    jit = noted[0]["jit"]["admit"]
    assert jit["fun"] == "_serve_prefill"
    assert jit["trace"] > 0 and jit["lower"] > 0 and jit["compile"] > 0
    # the trace's seconds are part of the round's admit phase
    assert jit["trace"] + jit["lower"] + jit["compile"] \
        <= noted[0]["phases"]["admit"]
    assert noted[0] is max(cold, key=lambda r: r["busy_s"])
    snap = obs.get_registry().snapshot()
    key = '{stage="trace",fun="_serve_prefill"}'
    assert snap["jit_stage_seconds_total" + key] > 0
    assert snap["jit_stage_events_total" + key] == 1
    assert 'jit_stage_seconds_total{stage="trace",fun="(nested)"}' in snap
    # the listener's ring, cut by the test's own stamps
    evs = [e for e in jitwatch.events(t1, t2) if e.fun == "_serve_prefill"]
    assert {e.stage for e in evs} == {"trace", "lower", "compile"}
    assert {e.thread for e in evs} == {"serve-loop"}
    assert evs[0].tid in jitwatch.loop_threads()
    assert not [e for e in jitwatch.events(t0, t1)
                if e.tid == eng.loop._tid]
    # armed, the span says so: late arguments on the one prefill that
    # traced and on no span of the warmed request
    line = _line_with(sess.lines(), "serve/round")
    prefills = [st for n, _, _, st in line if n == "serve/prefill"]
    assert len(prefills) == 2
    assert "trace_ms" not in prefills[0] and "jit_fun" not in prefills[0]
    assert prefills[1]["jit_fun"] == "_serve_prefill"
    assert prefills[1]["trace_ms"] > 0 and prefills[1]["lower_ms"] > 0
    first_round_end = min(e for n, _, e, _ in line
                          if n == "serve/prefill_into")
    assert not [n for n, _, e, st in line
                if e <= first_round_end and "trace_ms" in st]


def test_a_chaos_slow_round_is_the_longest_with_its_seconds_in_dispatch(
        tiny_llama):
    eng = _engine(tiny_llama)
    eng.submit(_prompt(19), 3)
    eng.run_until_idle()   # compiled, the retire's block donation too
    chaos.maybe_init("slow@rank=0:ms=120:step=6", rank=0, seed=0)
    t0 = time.monotonic()
    eng.submit(_prompt(9, seed=11), 8)   # shares no prefix: no restore
    eng.run_until_idle()
    recs = _mine(eng, t0)
    longest = max(recs, key=lambda r: r["busy_s"])
    assert longest["round"] == 6
    assert longest["phases"]["dispatch"] >= 0.12
    assert longest["phases"]["dispatch"] > 0.9 * longest["wall_s"]
    assert longest in eng.loop.longest
    assert "round 6 " in goodput.describe_round(longest, t0)
    assert "dispatch 12" in goodput.describe_round(longest, t0)


def test_a_forced_collection_is_counted(tiny_llama):
    eng = _engine(tiny_llama)
    eng.submit(_prompt(19), 3)
    eng.run_until_idle()
    before = jitwatch.gc_totals().seconds
    hook = chaos.on_step

    def collect_then(step):
        if step == 5:
            gc.collect()
        return hook(step)

    try:
        chaos.on_step = collect_then
        t0 = time.monotonic()
        eng.submit(_prompt(9, seed=11), 6)
        eng.run_until_idle()
    finally:
        chaos.on_step = hook
    assert jitwatch.gc_totals().seconds > before
    [rec] = [r for r in _mine(eng, t0) if r["round"] == 5]
    assert rec["gc_s"] > 0
    assert rec["gc_s"] <= rec["wall_s"]
    eng.loop.publish()
    snap = obs.get_registry().snapshot()
    assert snap['gc_pause_seconds_total{generation="2"}'] > 0
    assert snap['gc_collections_total{generation="2"}'] >= 1


# -- one listener, bounded rings ----------------------------------------------

def test_two_engines_in_one_process_install_one_listener(tiny_llama):
    from jax._src import monitoring as mon

    _engine(tiny_llama)
    _engine(tiny_llama, max_slots=2)
    jitwatch.install()
    ours = [cb for cb in mon.get_event_time_span_listeners()
            if getattr(cb, "__self__", None).__class__.__name__ == "_Watch"]
    assert len(ours) == 1
    assert sum(1 for cb in gc.callbacks
               if getattr(cb, "__self__", None).__class__.__name__
               == "_Watch") == 1
    assert len([cb for cb in mon.get_event_listeners()
                if getattr(cb, "__self__", None) is ours[0].__self__]) == 1


def test_rings_and_labels_are_bounded(monkeypatch):
    assert goodput._rounds.maxlen >= 8192
    # rings of this test's own: the process's hold other tests' records
    monkeypatch.setattr(goodput, "_rounds", collections.deque(
        maxlen=goodput._rounds.maxlen))
    tally = GoodputMeter(goodput.SERVE_PHASES, goodput.SERVE_SPANS,
                         clock=time.monotonic, rounds=True)
    tally.start()
    for i in range(goodput._rounds.maxlen + 10):
        tally.lap(i, occ=0)
    assert len(goodput._rounds) == goodput._rounds.maxlen
    assert len(tally.longest) == 3
    watch = jitwatch._Watch()
    assert watch.ring.maxlen == jitwatch.RING
    event = "/jax/core/compile/jaxpr_trace_duration"
    for i in range(jitwatch.MAX_FUNS + jitwatch.RING + 5):
        watch.on_start(event, 0.0, fun_name=f"minted_{i}")
        watch.on_span(event, 0.0, 1e-6, fun_name=f"minted_{i}")
    assert len(watch.ring) == jitwatch.RING
    assert len(watch.funs) == jitwatch.MAX_FUNS
    snap = obs.get_registry().snapshot()
    labelled = [k for k in snap if k.startswith("jit_stage_events_total")]
    assert len(labelled) <= jitwatch.MAX_FUNS + 1
    assert 'jit_stage_events_total{stage="trace",fun="other"}' in snap


def test_nested_traces_count_once_in_the_threads_totals():
    jitwatch.install()
    watch = jitwatch._watch
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    done = {}

    def body():
        # (a thread's ident may be a finished thread's: its totals run on)
        tot = jitwatch.thread_totals()
        mark, total0, t0 = tot.mark(), tot.total, time.monotonic()
        watch.on_start(trace, 0.0, fun_name="outer")
        for k in range(3):   # three jnp operations inside the trace
            watch.on_start(trace, 0.0, fun_name="inner")
            watch.on_span(trace, 1.0 + k, 1.5 + k, fun_name="inner")
        watch.on_span(trace, 0.0, 5.0, fun_name="outer")
        watch.on_start(lower, 0.0, fun_name="jit(outer)")
        watch.on_span(lower, 5.0, 7.0, fun_name="jit(outer)")
        done.update(tot.since(mark), total=tot.total - total0, t0=t0,
                    tid=threading.get_ident())

    th = threading.Thread(target=body)
    th.start()
    th.join(10.0)
    assert not th.is_alive()
    assert done["trace"] == pytest.approx(5.0)   # not 6.5
    assert done["lower"] == pytest.approx(2.0)
    assert done["fun"] == "outer" and done["total"] == pytest.approx(7.0)
    mine = [e for e in jitwatch.events(done["t0"])
            if e.tid == done["tid"]]
    assert [(e.stage, e.fun, e.seconds) for e in mine] == [
        ("trace", "outer", 5.0), ("lower", "outer", 2.0)]
    # a thread that never traced reads zeros that never move
    assert jitwatch.thread_totals().total == \
        jitwatch.thread_totals().total


# -- the trainer's meter is what it was ---------------------------------------

def test_trainer_meter_fields_are_unchanged():
    gp = GoodputMeter()
    assert gp.phases == PHASES == ("data", "compute", "collective",
                                   "checkpoint", "eval", "other")
    rec = obs.enable_tracing(process_index=0)
    gp.step_start()
    with gp.phase("data"):
        time.sleep(0.005)
    with gp.phase("compute"):
        time.sleep(0.005)
    bd = gp.step_end(step=3)
    obs.disable_tracing()
    assert set(bd.as_fields()) == {"step", "wall_s", "accounted_frac"} | {
        f"{p}_s" for p in PHASES}
    s = gp.summary()
    assert set(s) == {"steps", "wall_s", "accounted_frac", "goodput_frac"} \
        | {f"{p}_s" for p in PHASES} | {f"{p}_frac" for p in PHASES}
    assert set(gp.window_summary()) == set(s)
    # what data_wait_share.train reads: the loader wait, in seconds
    assert s["data_s"] >= 0.005 and s["steps"] == 1
    assert s["data_s"] + s["compute_s"] + s["other_s"] == \
        pytest.approx(s["wall_s"], abs=2e-6)
    names = [e["name"] for e in rec.events()]
    assert names == ["goodput/data", "goodput/compute"]
    assert all(e["cat"] == "goodput" for e in rec.events())
    # no round record, no loop thread: those are a lapping loop's
    assert gp.longest == [] and gp.t_start is None


def test_a_phase_inside_another_takes_its_seconds_out_of_it():
    gp = GoodputMeter()
    gp.step_start()
    with gp.phase("compute"):
        time.sleep(0.004)
        with gp.phase("checkpoint"):
            time.sleep(0.008)
    bd = gp.step_end(step=0)
    assert bd.phases["checkpoint"] >= 0.008
    assert 0.004 <= bd.phases["compute"] < 0.008
    assert sum(bd.phases.values()) == pytest.approx(bd.wall_s, rel=1e-6)
