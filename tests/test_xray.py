"""obs.xray — anomaly-triggered profiling, attribution, compile
telemetry, and the perf-regression ledger (ISSUE 10 tentpole).

Covers: the TPUNN_XRAY spec grammar, the inert-when-unset contract
(zero registry writes AND zero ring events from every hook), the
capture lifecycle with an injected clock (arm → trigger → ring event
FIRST → window advance → summary on disk; cooldown/max/busy all
suppress and are counted), the watchtower page → capture integration
(the page's attribution names the capture dir; the second page is
rate-limited), per-op attribution from both sources (ring fallback +
perfetto trace) with the wire-byte cross-check and roofline columns,
compile telemetry end-to-end (log-watch regex → counters → ring
breadcrumb → recompile_storm naming the re-traced function), the
newest-trace-by-mtime regression (ISSUE 10 satellite), profiling
primitive edge cases (StepTimer/time_steps/bus_bandwidth), and the
chaos acceptance drill from the issue.
"""

import glob
import gzip
import json
import logging
import math
import os
import time

import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.obs import flight, watchtower, xray
from pytorch_distributed_nn_tpu.runtime import chaos


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Disarmed xray + tower + chaos, fresh ring + registry, unset env."""
    monkeypatch.delenv(xray.ENV_XRAY, raising=False)
    monkeypatch.delenv(watchtower.ENV_WATCH, raising=False)
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    xray.reset()
    watchtower.reset()
    chaos.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    yield
    xray.reset()
    watchtower.reset()
    chaos.reset()


def _engine(spec, tmp_path, **kw):
    kw.setdefault("rank", 0)
    return xray.XrayEngine(xray.parse_spec(spec), base_dir=tmp_path, **kw)


# ---------------------------------------------------------------------------
# Spec grammar
# ---------------------------------------------------------------------------

def test_parse_spec_defaults_and_overrides():
    for s in ("", "1", "on", "true", "TRUE"):
        cfg = xray.parse_spec(s)
        assert cfg == xray.XrayConfig()
    cfg = xray.parse_spec("every=100:steps=5:cooldown_s=1.5:profiler=0:"
                          "max_captures=2:dir=/tmp/x")
    assert cfg.every == 100 and cfg.steps == 5
    assert cfg.cooldown_s == 1.5 and cfg.profiler == 0
    assert cfg.max_captures == 2 and cfg.dir == "/tmp/x"


def test_parse_spec_rejects_garbage():
    with pytest.raises(ValueError, match="unknown key"):
        xray.parse_spec("bogus=1")
    with pytest.raises(ValueError, match="bad value"):
        xray.parse_spec("steps=three")
    with pytest.raises(ValueError, match="key=value"):
        xray.parse_spec("steps")
    with pytest.raises(ValueError, match="steps"):
        xray.parse_spec("steps=0")
    with pytest.raises(ValueError, match="max_captures"):
        xray.parse_spec("max_captures=0")
    with pytest.raises(ValueError, match="cooldown_s"):
        xray.parse_spec("cooldown_s=-1")


# ---------------------------------------------------------------------------
# Arming + the inert contract
# ---------------------------------------------------------------------------

def test_maybe_init_unset_is_inert(monkeypatch):
    assert xray.maybe_init() is None
    assert not xray.enabled()
    monkeypatch.setenv(xray.ENV_XRAY, "0")
    assert xray.maybe_init() is None


def test_maybe_init_env_and_idempotence(monkeypatch, tmp_path):
    monkeypatch.setenv(xray.ENV_XRAY, "profiler=0:steps=2")
    eng = xray.maybe_init(base_dir=tmp_path)
    assert eng is not None and xray.enabled()
    assert eng.cfg.steps == 2
    assert xray.maybe_init() is eng, "second init returns the armed one"
    xray.reset()
    assert not xray.enabled()


def test_disarmed_hooks_are_noops():
    """With TPUNN_XRAY unset every hook must do literally nothing:
    no registry series, no ring events, no capture dirs."""
    before_reg = obs.get_registry().prometheus_text()
    before_ring = len(flight.get_recorder().snapshot())
    xray.on_step(5)
    xray.on_serve_round(7)
    xray.on_wire_bytes(1e6)
    assert xray.on_page("loss_nonfinite", step=3) is None
    assert xray.capture_now() is None
    assert obs.get_registry().prometheus_text() == before_reg
    assert len(flight.get_recorder().snapshot()) == before_ring


# ---------------------------------------------------------------------------
# Capture lifecycle (profiler=0 → ring-only, injected clock)
# ---------------------------------------------------------------------------

def test_capture_lifecycle_ring_only(tmp_path):
    eng = _engine("profiler=0:steps=2:cooldown_s=100", tmp_path)
    cap = eng.request_capture("manual", step=10, t=1000.0)
    assert cap is not None and os.path.isdir(cap)
    assert "xray_0_00_manual" in cap
    # ring says a capture started, and says it FIRST
    evs = [e for e in flight.get_recorder().snapshot()
           if e["kind"] == "xray"]
    assert evs and evs[0]["op"] == "capture"
    assert "manual" in evs[0]["note"] and cap in evs[0]["note"]
    # window spans cfg.steps step boundaries, then the summary lands
    flight.record("collective", "all_reduce", axis="data", nbytes=4096,
                  step=11, note="dispatch")
    eng.step(11, t=1001.0)
    assert eng._active is not None, "1 of 2 window steps consumed"
    eng.step(12, t=1002.0)
    assert eng._active is None
    spath = os.path.join(cap, xray.SUMMARY_NAME)
    assert os.path.exists(spath)
    summary = json.loads(open(spath).read())
    assert summary["reason"] == "manual"
    assert summary["trigger_step"] == 10
    assert summary["profiler"] is False
    assert summary["t_end"] == 1002.0
    assert summary["attribution"]["source"] == "flight_ring"
    done = [e for e in flight.get_recorder().snapshot()
            if e["kind"] == "xray" and e["op"] == "capture_done"]
    assert len(done) == 1
    reg = obs.get_registry()
    assert reg.counter("xray_captures_total", "",
                       labels=("trigger",)).value(trigger="manual") == 1
    assert eng.summary()["captures"] == 1
    assert eng.summary()["paths"] == [cap]


def test_rate_limiter_cooldown_busy_and_lifetime(tmp_path):
    eng = _engine("profiler=0:steps=1:cooldown_s=50:max_captures=2",
                  tmp_path)
    assert eng.request_capture("a", t=100.0) is not None
    # busy: window still open
    assert eng.request_capture("b", t=100.5) is None
    eng.step(1, t=101.0)  # closes the window
    # cooldown: 50s since t=100 not elapsed
    assert eng.request_capture("c", t=120.0) is None
    assert eng.request_capture("d", t=151.0) is not None
    eng.step(2, t=152.0)
    # lifetime: max_captures=2 exhausted forever
    assert eng.request_capture("e", t=999.0) is None
    assert eng.suppressed == {"busy": 1, "cooldown": 1,
                              "max_captures": 1}
    reg = obs.get_registry()
    c = reg.counter("xray_suppressed_total", "", labels=("reason",))
    for reason in ("busy", "cooldown", "max_captures"):
        assert c.value(reason=reason) == 1


def test_interval_trigger_and_close(tmp_path):
    eng = _engine("profiler=0:every=10:steps=1:cooldown_s=0", tmp_path)
    for s in range(1, 10):
        eng.step(s, t=float(s))
    assert eng._n_started == 0, "no boundary crossed yet"
    eng.step(10, t=10.0)
    assert eng._active is not None and "interval" in eng._active["reason"]
    # close() finishes the open window instead of losing it
    eng.close(t=11.0)
    assert eng._active is None and len(eng.captures) == 1
    assert eng.captures[0]["reason"] == "interval"


# ---------------------------------------------------------------------------
# Watchtower page → capture (the tentpole integration)
# ---------------------------------------------------------------------------

def test_page_triggers_one_capture_and_names_it(tmp_path):
    xray.maybe_init("profiler=0:steps=1:cooldown_s=3600",
                    rank=0, base_dir=tmp_path)
    t = watchtower.Watchtower(watchtower.parse_spec("1"),
                              dump_on_page=False)
    t.observe({"ev": "loss", "t": 1.0, "step": 4, "loss": math.inf})
    pages = [a for a in t.alerts if a.severity == watchtower.PAGE]
    assert len(pages) == 1
    cap = pages[0].attribution.get("xray_capture")
    assert cap and str(tmp_path) in cap, \
        "the page must name the capture dir it started"
    assert os.path.isdir(cap)
    # close the window, then a second page inside the cooldown: alert
    # still fires, but NO second capture starts
    xray.engine().step(5, t=time.time())
    t.observe({"ev": "loss", "t": 2.0, "step": 6, "loss": math.nan})
    pages = [a for a in t.alerts if a.kind == "loss_nonfinite"]
    assert len(pages) == 2
    assert "xray_capture" not in pages[1].attribution
    assert xray.engine()._n_started == 1, "rate limiter held the line"
    assert xray.engine().suppressed.get("cooldown") == 1


def test_page_with_on_page_zero_never_captures(tmp_path):
    xray.maybe_init("profiler=0:on_page=0", rank=0, base_dir=tmp_path)
    t = watchtower.Watchtower(watchtower.parse_spec("1"),
                              dump_on_page=False)
    t.observe({"ev": "loss", "t": 1.0, "step": 4, "loss": math.inf})
    assert [a for a in t.alerts if a.severity == watchtower.PAGE]
    assert xray.engine()._n_started == 0
    assert not glob.glob(str(tmp_path / "xray_*"))


def test_replay_streams_stay_byte_identical(tmp_path):
    """The replay-determinism contract from the watchtower tests must
    survive the xray edge: with TPUNN_XRAY unset, the same event stream
    twice yields byte-identical alert JSON (no capture paths leak in)."""
    def run():
        t = watchtower.Watchtower(watchtower.parse_spec("1"),
                                  dump_on_page=False)
        t.observe({"ev": "loss", "t": 1.0, "step": 4, "loss": math.inf})
        return "\n".join(a.as_json() for a in t.alerts)

    first = run()
    flight.reset_recorder(enabled=True)
    second = run()
    assert first == second


# ---------------------------------------------------------------------------
# Per-op attribution
# ---------------------------------------------------------------------------

def _mk_events():
    # hand-built ring: one 30ms all_reduce window, one 10ms fused step
    # dispatch, one trace-time record (t1 == t0: counts calls/bytes only)
    return [
        {"kind": "collective", "op": "all_reduce", "t0": 1.0, "t1": 1.03,
         "nbytes": 7 * 4096, "step": 1},
        {"kind": "dispatch", "op": "train_step", "t0": 1.05, "t1": 1.06,
         "nbytes": 0, "step": 1},
        {"kind": "collective", "op": "all_gather", "t0": 1.07, "t1": 1.07,
         "nbytes": 1024, "step": 1},
        {"kind": "step", "op": "mark", "t0": 1.08, "t1": 1.08, "step": 1},
    ]


def test_ring_attribution_names_collective_top():
    att = xray.build_attribution(events=_mk_events(),
                                 wire_bytes_per_step=7 * 4096 + 1024,
                                 steps=1)
    assert att["source"] == "flight_ring"
    assert att["top_op"] == "all_reduce"
    assert att["top_category"] == "collective"
    assert att["top_share"] == pytest.approx(0.75, abs=0.01)
    comm = att["comm"]
    assert comm["ring_nbytes"] == 7 * 4096 + 1024
    assert comm["ring_vs_recorder"] == pytest.approx(1.0)
    assert comm["implied_gbps"] > 0
    # step events never count as op rows
    assert all(r["op"] != "mark" for r in att["rows"])


def test_attribution_roofline_columns():
    att = xray.build_attribution(events=_mk_events(),
                                 flops_per_step=2e9, steps=2,
                                 peak_flops=1e12)
    row = next(r for r in att["rows"] if r["category"] == "compute")
    assert row["flops"] == pytest.approx(4e9), \
        "analytic FLOPs × steps land on the compute rows"
    assert row["achieved_flops_per_s"] == pytest.approx(4e9 / 0.01)
    assert row["roofline_frac"] == pytest.approx(4e11 / 1e12)
    coll = next(r for r in att["rows"] if r["category"] == "collective")
    assert "flops" not in coll, "collectives get no FLOP attribution"
    table = xray.render_op_table(att)
    assert "all_reduce" in table and "train_step" in table
    assert "%" in table


def test_attribution_empty_sources():
    att = xray.build_attribution(events=[])
    assert att["source"] == "none" and att["rows"] == []
    assert att["top_op"] == "" and att["total_s"] == 0.0
    assert xray.render_op_table(att)  # header renders, no crash


def _write_perfetto(run_dir, events):
    os.makedirs(run_dir, exist_ok=True)
    path = os.path.join(run_dir, "perfetto_trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def test_trace_attribution_preferred_over_ring(tmp_path):
    _write_perfetto(tmp_path / "run", [
        {"ph": "X", "name": "fusion.3", "dur": 100.0},
        {"ph": "X", "name": "all-reduce.1", "dur": 300.0},
        {"ph": "X", "name": "$step.py:12 python", "dur": 900.0},
        {"ph": "X", "name": "end: all-reduce.1", "dur": 900.0},
        {"ph": "M", "name": "process_name"},
    ])
    att = xray.build_attribution(trace_dir=str(tmp_path),
                                 events=_mk_events())
    assert att["source"] == "trace"
    assert att["top_op"] == "all-reduce.1"
    assert att["top_category"] == "collective"
    assert att["top_share"] == pytest.approx(0.75)
    assert len(att["rows"]) == 2, "python/meta/end slices excluded"


# ---------------------------------------------------------------------------
# Newest-trace-by-mtime (ISSUE 10 satellite: lexicographic-order bug)
# ---------------------------------------------------------------------------

def test_newest_perfetto_is_by_mtime_not_name(tmp_path):
    """Profiler run dirs are timestamp strings; a clock step backwards
    (or a re-used dir) makes lexicographic order lie. The regression:
    the lexicographically LATER name holds the OLDER trace and used to
    win."""
    older = _write_perfetto(
        tmp_path / "plugins" / "profile" / "2026_01_02",
        [{"ph": "X", "name": "all-reduce.9", "dur": 500.0}])
    newer = _write_perfetto(
        tmp_path / "plugins" / "profile" / "2026_01_01",
        [{"ph": "X", "name": "all-gather.1", "dur": 250.0}])
    now = time.time()
    os.utime(older, (now - 100, now - 100))
    os.utime(newer, (now, now))
    assert xray._newest_perfetto(str(tmp_path)) == newer
    ct = xray.collective_trace_seconds(str(tmp_path), world=2)
    assert ct is not None
    assert set(ct.names) == {"all-gather.1"}, \
        "the mtime-newest trace must win, not the name-newest"
    assert ct.total_s == pytest.approx(250e-6)
    assert ct.per_device_s == pytest.approx(125e-6)


def test_collective_trace_none_when_empty(tmp_path):
    assert xray.collective_trace_seconds(str(tmp_path), world=8) is None
    _write_perfetto(tmp_path / "r",
                    [{"ph": "X", "name": "fusion.1", "dur": 10.0}])
    assert xray.collective_trace_seconds(str(tmp_path), world=8) is None


# ---------------------------------------------------------------------------
# Profiling primitive edge cases (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def test_steptimer_empty_summary_is_zeros():
    s = xray.StepTimer().summary()
    assert s == {"steps": 0, "mean_s": 0.0, "p50_s": 0.0, "p95_s": 0.0,
                 "total_s": 0.0}


def test_bus_bandwidth_zero_step_time():
    bw = xray.bus_bandwidth([], 0.0)
    assert bw.wire_gbps == 0.0 and bw.wire_bytes_per_step == 0.0
    assert bw.records == 0


def test_time_steps_carry_state_toggle():
    seen = []

    def step_fn(state, x):
        seen.append(state)
        return (state + 1, x)

    timer = xray.time_steps(step_fn, lambda i: (0, i), iters=4,
                            warmup=2, carry_state=False)
    assert len(timer.times) == 4
    assert seen == [0] * 6, "carry_state=False re-feeds the initial state"

    seen.clear()
    timer = xray.time_steps(step_fn, lambda i: (0, i), iters=3,
                            warmup=1, carry_state=True)
    assert len(timer.times) == 3
    assert seen == [0, 1, 2, 3], "carry_state=True threads the output"
    assert timer.summary()["steps"] == 3


def test_profiling_shim_reexports():
    """utils.profiling was absorbed into obs.xray; the shim must keep
    every public name importable and identical."""
    from pytorch_distributed_nn_tpu.utils import profiling

    for name in ("StepTimer", "BusBandwidth", "CollectiveTrace",
                 "bus_bandwidth", "collective_trace_seconds",
                 "time_steps", "xprof_trace"):
        assert getattr(profiling, name) is getattr(xray, name)


# ---------------------------------------------------------------------------
# Compile telemetry
# ---------------------------------------------------------------------------

def test_on_compile_counts_and_breadcrumbs(tmp_path):
    eng = _engine("profiler=0", tmp_path)
    eng._on_compile("jit(train_step)", 1.5)
    eng._on_compile("jit(train_step)", 0.5)
    eng._on_compile("eval_step", 0.25)
    assert eng.compile_counts == {"train_step": 2, "eval_step": 1}
    assert eng.compile_seconds_total == pytest.approx(2.25)
    reg = obs.get_registry()
    assert reg.counter("xray_compiles_total", "").value() == 3
    assert reg.gauge("xray_compile_seconds", "").value() == \
        pytest.approx(2.25)
    crumbs = [e for e in flight.get_recorder().snapshot()
              if e["kind"] == "xray" and e["op"] == "compile"]
    assert len(crumbs) == 3
    assert "train_step" in crumbs[0]["note"]


def test_compile_watch_hears_the_jit_listener_by_function(tmp_path):
    """A jitted call that finds no executable reaches the armed engine
    through the process's one ``jax.monitoring`` listener, under the
    function's own name; tracing and lowering are not compilations."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.obs import jitwatch

    eng = _engine("profiler=0", tmp_path)
    eng._install_compile_watch()
    try:
        @jax.jit
        def _xray_probe_step(x):
            return x * 3 + 1

        _xray_probe_step(jnp.arange(5.0)).block_until_ready()
        assert eng.compile_counts["_xray_probe_step"] == 1
        assert eng.compile_seconds_total > 0
        # the second call finds its executable: no event
        _xray_probe_step(jnp.arange(5.0)).block_until_ready()
        assert eng.compile_counts["_xray_probe_step"] == 1
        traced = [e for e in jitwatch.events()
                  if e.fun == "_xray_probe_step"]
        assert {e.stage for e in traced} == {"trace", "lower", "compile"}
        crumbs = [e for e in flight.get_recorder().snapshot()
                  if e["kind"] == "xray" and e["op"] == "compile"]
        assert any("_xray_probe_step" in c["note"] for c in crumbs)
    finally:
        eng._uninstall_compile_watch()


def test_compile_watch_leaves_jax_logging_alone_and_disarms(tmp_path):
    """Arming xray touches neither level nor propagation of jax's
    dispatch logger (the log tap is gone), and a disarmed engine hears
    nothing more while the listener stays for the next one."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.obs import jitwatch

    lg = logging.getLogger("jax._src.dispatch")
    before = (lg.propagate, lg.level, list(lg.handlers))
    eng = _engine("profiler=0", tmp_path)
    eng._install_compile_watch()
    eng._install_compile_watch()   # idempotent: one sink
    assert (lg.propagate, lg.level, list(lg.handlers)) == before
    eng._uninstall_compile_watch()
    jax.jit(lambda x: x - 7)(jnp.arange(3.0)).block_until_ready()
    assert eng.compile_counts == {}
    assert jitwatch.installed()
    other = _engine("profiler=0", tmp_path)
    other._install_compile_watch()
    try:
        jax.jit(lambda x: x - 9)(jnp.arange(3.0)).block_until_ready()
        assert sum(other.compile_counts.values()) >= 1
        assert eng.compile_counts == {}
    finally:
        other._uninstall_compile_watch()


def test_real_jit_compile_is_observed(tmp_path):
    """End to end against the real dispatcher: arming xray then jitting
    a fresh function must tick the compile counters."""
    import jax

    xray.maybe_init("profiler=0", rank=0, base_dir=tmp_path)

    @jax.jit
    def _xray_probe_fn(x):
        return x * 2 + 1

    _xray_probe_fn(1.0).block_until_ready()
    eng = xray.engine()
    assert sum(eng.compile_counts.values()) >= 1, eng.compile_counts
    assert any("_xray_probe_fn" in k for k in eng.compile_counts), \
        eng.compile_counts
    assert eng.compile_seconds_total > 0


def test_recompile_storm_names_the_function():
    t = watchtower.Watchtower(
        watchtower.parse_spec("recompile_min=3:recompile_window_s=60"),
        dump_on_page=False)
    for i in range(2):
        t.observe({"ev": "compile", "t": float(i), "name": "train_step",
                   "seconds": 0.5})
    t.observe({"ev": "compile", "t": 2.0, "name": "eval_step",
               "seconds": 0.5})  # different function: no storm
    assert not t.alerts
    t.observe({"ev": "compile", "t": 3.0, "name": "train_step",
               "seconds": 0.5})
    storms = [a for a in t.alerts if a.kind == "recompile_storm"]
    assert len(storms) == 1
    assert storms[0].severity == watchtower.WARN
    assert storms[0].attribution["function"] == "train_step"
    assert storms[0].attribution["count"] == 3
    assert storms[0].attribution["compile_seconds"] == pytest.approx(1.5)
    assert "train_step" in storms[0].detail
    # hysteresis: the history cleared, two more compiles stay silent
    for i in range(2):
        t.observe({"ev": "compile", "t": 4.0 + i, "name": "train_step",
                   "seconds": 0.5})
    assert len([a for a in t.alerts
                if a.kind == "recompile_storm"]) == 1
    # ...but outside the window nothing accumulates either
    t.observe({"ev": "compile", "t": 500.0, "name": "train_step",
               "seconds": 0.5})
    assert len([a for a in t.alerts
                if a.kind == "recompile_storm"]) == 1


def test_xray_feeds_recompile_storm_through_tower(tmp_path):
    """The full loop: xray's log watch → watchtower.on_compile → storm
    alert — with both singletons armed the way the trainer arms them."""
    watchtower.maybe_init("recompile_min=2:recompile_window_s=600",
                          rank=0)
    watchtower.tower().dump_on_page = False
    xray.maybe_init("profiler=0", rank=0, base_dir=tmp_path)
    eng = xray.engine()
    eng._on_compile("jit(train_step)", 0.4)
    eng._on_compile("jit(train_step)", 0.6)
    storms = [a for a in watchtower.tower().alerts
              if a.kind == "recompile_storm"]
    assert len(storms) == 1
    assert storms[0].attribution["function"] == "train_step"


# ---------------------------------------------------------------------------
# Chaos acceptance drill (the ISSUE 10 criterion)
# ---------------------------------------------------------------------------

def test_chaos_page_triggers_one_capture_naming_collective(
        tmp_path, monkeypatch):
    """Under injected chaos, a watchtower page starts EXACTLY ONE xray
    capture, and the capture's per-op table names a collective as the
    top time share (the ring carries a long all_reduce dispatch
    window). A second page inside the cooldown is suppressed."""
    monkeypatch.setenv(flight.ENV_FLIGHT_DIR, str(tmp_path))
    chaos.maybe_init("slow@rank=0:ms=1", rank=0, seed=7)
    xray.maybe_init("profiler=0:steps=1:cooldown_s=3600",
                    rank=0, base_dir=tmp_path)
    tower = watchtower.maybe_init("1", rank=0)
    tower.dump_on_page = False

    chaos.on_step(1)  # the injected fault lands a chaos ring event
    tower.observe({"ev": "loss", "t": 1.0, "step": 1, "loss": math.inf})
    pages = [a for a in tower.alerts if a.severity == watchtower.PAGE]
    assert len(pages) == 1
    cap = pages[0].attribution["xray_capture"]
    assert os.path.isdir(cap)

    # the capture window sees a dominant collective + a short dispatch
    with flight.get_recorder().collective(
            "all_reduce", axis="data", nbytes=1 << 20, step=2):
        time.sleep(0.03)
    with flight.get_recorder().dispatch("train_step", step=2):
        time.sleep(0.005)
    xray.on_step(2)

    summary = json.loads(
        open(os.path.join(cap, xray.SUMMARY_NAME)).read())
    att = summary["attribution"]
    assert att["top_category"] == "collective"
    assert att["top_op"] == "all_reduce"
    assert att["top_share"] > 0.5
    table = xray.render_op_table(att)
    assert "all_reduce" in table.splitlines()[2], \
        "the rendered table leads with the collective"

    # second page: alert fires, capture suppressed, exactly one dir
    tower.observe({"ev": "loss", "t": 2.0, "step": 3, "loss": math.nan})
    assert xray.engine()._n_started == 1
    assert len(glob.glob(str(tmp_path / "xray_*"))) == 1
    # the chaos event is in the ring, so the doctor can't misattribute
    assert any(e["kind"] == "chaos"
               for e in flight.get_recorder().snapshot())


def test_forensics_attribution_carries_capture_conditionally():
    from pytorch_distributed_nn_tpu.obs import forensics

    base = forensics.attribute([{"kind": "step", "op": "mark"}])
    assert "xray_capture" not in base, \
        "non-xray rings keep the attribution dict byte-identical"
    events = [{"kind": "xray", "op": "capture",
               "note": "page:loss_nonfinite -> /tmp/cap/xray_0_00"}]
    att = forensics.attribute(events)
    assert att["xray_capture"] == "/tmp/cap/xray_0_00"


@pytest.mark.slow
def test_profiler_capture_end_to_end_slow(tmp_path):
    """Real jax.profiler end to end (slow, like the trace test in
    test_utils.py): an armed engine starts a device trace, the capture
    summary lands, and attribution prefers the trace when the backend
    produced parseable slices."""
    import jax
    import jax.numpy as jnp

    eng = _engine("steps=1:cooldown_s=0:perfetto=1", tmp_path)
    cap = eng.request_capture("manual", step=0)
    assert cap is not None

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    x = jnp.ones((256, 256))
    for _ in range(3):
        f(x).block_until_ready()
    eng.step(1)
    assert eng._active is None
    summary = eng.captures[-1]
    assert os.path.exists(os.path.join(cap, xray.SUMMARY_NAME))
    assert summary["attribution"]["source"] in ("trace", "flight_ring",
                                                "none")
    if summary["attribution"]["source"] == "trace":
        assert summary["attribution"]["rows"]
