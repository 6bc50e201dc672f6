"""Unified telemetry (obs/): registry exposition, span tracing, goodput
accounting, runtime gauges, cross-host aggregation, and the acceptance
run — a 2×2 CPU-mesh training whose breakdown accounts for ≥95% of wall
step time, renders valid Prometheus text, and feeds obs_report.py."""

import gzip
import json
import re
import threading

import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.obs import aggregate, registry as reg_mod
from pytorch_distributed_nn_tpu.obs.goodput import PHASES, GoodputMeter


@pytest.fixture()
def registry():
    """Fresh default registry per test (the default is process-global)."""
    fresh = obs.reset_registry()
    yield fresh
    obs.reset_registry()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_roundtrip(registry):
    c = registry.counter("requests_total", "reqs", labels=("code",))
    c.inc(code=200)
    c.inc(2, code=200)
    c.inc(code=500)
    assert c.value(code=200) == 3
    assert c.value(code=500) == 1
    with pytest.raises(ValueError):
        c.inc(-1, code=200)  # counters only go up
    with pytest.raises(ValueError):
        c.inc(status=200)  # wrong label name
    g = registry.gauge("temp", "t")
    g.set(3.5)
    g.inc(0.5)
    assert g.value() == 4.0


def test_registry_get_or_create_shares_series(registry):
    a = registry.counter("steps_total")
    b = registry.counter("steps_total")
    assert a is b
    with pytest.raises(TypeError):
        registry.gauge("steps_total")  # name already a counter


def test_histogram_buckets_cumulative(registry):
    h = registry.histogram("lat", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.7, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(6.25)
    rows = {(name, key): v for name, key, v in h.collect()}
    assert rows[("lat_bucket", ("0.1",))] == 1
    assert rows[("lat_bucket", ("1",))] == 3  # cumulative
    assert rows[("lat_bucket", ("+Inf",))] == 4
    assert rows[("lat_count", ())] == 4


_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    # a value may carry a negative exponent (a counter of microseconds)
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9eE+.naif-]+)$"
)


def _assert_valid_prometheus(text: str) -> None:
    assert text.endswith("\n")
    for line in text.splitlines():
        assert _PROM_LINE.match(line), f"invalid exposition line: {line!r}"


def test_prometheus_text_valid(registry):
    registry.counter("a_total", "with \"quotes\" and\nnewline").inc(3)
    registry.gauge("g", labels=("axis",)).set(2.5, axis="data")
    registry.histogram("h", buckets=(1.0,)).observe(0.5)
    text = registry.prometheus_text()
    _assert_valid_prometheus(text)
    assert "a_total 3\n" in text
    assert 'g{axis="data"} 2.5' in text
    assert '# TYPE h histogram' in text
    assert 'h_bucket{le="+Inf"} 1' in text


def test_registry_thread_safety(registry):
    c = registry.counter("n_total")

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 4000


def test_snapshot_and_jsonl_sink(registry, tmp_path):
    from pytorch_distributed_nn_tpu.utils.metrics import MetricsLogger

    registry.counter("steps_total").inc(7)
    registry.histogram("lat", buckets=(1.0,)).observe(0.2)
    snap = registry.snapshot()
    assert snap["steps_total"] == 7
    assert snap["lat_count"] == 1
    assert not any("bucket" in k for k in snap)  # buckets stay local
    path = tmp_path / "m.jsonl"
    with MetricsLogger(path) as m:
        registry.emit_jsonl(m)
    ev = json.loads(path.read_text())
    assert ev["event"] == "metrics_snapshot"
    assert ev["metrics"]["steps_total"] == 7


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_disabled_is_free_and_shared():
    assert not obs.tracing_enabled()
    s1 = obs.span("x")
    s2 = obs.span("y", cat="data", step=3)
    assert s1 is s2  # the shared null context: no per-call allocation
    with s1:
        pass


def test_span_records_chrome_events(tmp_path):
    rec = obs.enable_tracing(process_index=0)
    try:
        assert obs.enable_tracing() is rec  # idempotent
        with obs.span("data/next_batch", cat="data", step=1):
            with obs.span("inner"):
                pass
        rec.instant("marker")
    finally:
        out = obs.disable_tracing()
    assert out is rec
    assert obs.span("after") is not None  # disabled again: null span
    path = obs.write_trace(tmp_path / "trace.json.gz", rec)
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    events = tr["traceEvents"]
    names = [e["name"] for e in events]
    assert "process_name" in names  # metadata track label
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(spans) == {"data/next_batch", "inner"}
    outer, inner = spans["data/next_batch"], spans["inner"]
    assert outer["args"] == {"step": 1}
    assert outer["dur"] >= inner["dur"]  # nesting: outer contains inner
    assert outer["ts"] <= inner["ts"]
    assert any(e.get("ph") == "i" for e in events)


def test_span_threads_get_own_tid(tmp_path):
    rec = obs.enable_tracing(process_index=0)
    try:
        with obs.span("main_thread"):
            pass

        def worker():
            with obs.span("worker_thread"):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    finally:
        obs.disable_tracing()
    spans = {e["name"]: e for e in rec.events()}
    assert spans["main_thread"]["tid"] != spans["worker_thread"]["tid"]


def test_merge_chrome_traces(tmp_path):
    rec = obs.enable_tracing(process_index=0)
    with obs.span("host_span"):
        pass
    obs.disable_tracing()
    host = obs.write_trace(tmp_path / "host.json", rec)
    device = tmp_path / "device.json.gz"
    with gzip.open(device, "wt") as f:
        json.dump({"traceEvents": [
            {"ph": "X", "name": "all-reduce.1", "ts": 0, "dur": 5.0},
        ]}, f)
    merged = obs.merge_chrome_traces([host, device],
                                     tmp_path / "merged.json")
    names = [e["name"]
             for e in json.loads(merged.read_text())["traceEvents"]]
    assert "host_span" in names and "all-reduce.1" in names


# ---------------------------------------------------------------------------
# goodput
# ---------------------------------------------------------------------------

def test_goodput_breakdown_sums_to_wall():
    import time

    gp = GoodputMeter()
    gp.step_start()
    with gp.phase("data"):
        time.sleep(0.01)
    with gp.phase("compute"):
        time.sleep(0.02)
    bd = gp.step_end(step=0)
    assert bd.phases["data"] >= 0.01
    assert bd.phases["compute"] >= 0.02
    assert sum(bd.phases.values()) == pytest.approx(bd.wall_s, rel=1e-6)
    assert bd.accounted_frac > 0.9
    fields = bd.as_fields()
    assert {f"{p}_s" for p in PHASES} <= set(fields)


def test_goodput_phase_validation():
    gp = GoodputMeter()
    gp.step_start()
    with pytest.raises(ValueError):
        with gp.phase("other"):  # "other" is computed, never measured
            pass
    with pytest.raises(ValueError):
        gp.add_phase_seconds("bogus", 1.0)
    with pytest.raises(RuntimeError):
        GoodputMeter().step_end()  # end without start


def test_goodput_windows_and_summary():
    gp = GoodputMeter()
    for step in range(3):
        gp.step_start()
        with gp.phase("compute"):
            pass
        gp.step_end(step=step)
    win = gp.window_summary()  # resets the window
    assert win["steps"] == 3
    assert gp.window_summary()["steps"] == 0
    gp.step_start()
    with gp.phase("data"):
        pass
    gp.step_end(step=3, steps_covered=4)  # fused multistep window
    assert gp.window_summary(reset=False)["steps"] == 4
    total = gp.summary()
    assert total["steps"] == 7
    assert total["wall_s"] > 0
    gp.wire_bytes_per_step = 1234.0
    assert gp.summary()["wire_bytes_per_step"] == 1234.0


def test_goodput_trace_derived_collective_share():
    gp = GoodputMeter()
    gp.step_start()
    with gp.phase("compute"):
        pass
    gp.add_phase_seconds("collective", 0.004)
    bd = gp.step_end(step=0)
    assert bd.phases["collective"] == pytest.approx(0.004)
    # collective is a share of an overlapping window, not extra wall:
    # the remainder clamps at zero instead of going negative
    assert bd.phases["other"] >= 0.0


# ---------------------------------------------------------------------------
# runtime gauges + aggregation
# ---------------------------------------------------------------------------

class _FakeStore:
    """Duck-typed stand-in for runtime.native.StoreClient."""

    def __init__(self):
        self.kv = {}

    def set(self, key, value):
        self.kv[key] = value

    def get(self, key, timeout_ms=-1):
        return self.kv[key]

    def check(self, key):
        return key in self.kv


def test_mesh_gauges(registry):
    import jax

    from pytorch_distributed_nn_tpu.obs import runtime_gauges
    from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=2, fsdp=2).resolve(4),
                     devices=jax.devices()[:4])
    runtime_gauges.export_mesh_gauges(mesh, registry)
    snap = registry.snapshot()
    assert snap['mesh_axis_size{axis="data"}'] == 2
    assert snap['mesh_axis_size{axis="fsdp"}'] == 2
    assert snap['mesh_axis_size{axis="tensor"}'] == 1
    assert snap["mesh_devices"] == 4
    assert snap["process_count"] == 1


def test_detector_gauges(registry):
    import time as _time

    from pytorch_distributed_nn_tpu.obs import runtime_gauges
    from pytorch_distributed_nn_tpu.runtime.failure import (
        FailureDetector,
        _hb_key,
    )

    store = _FakeStore()
    now = _time.time()
    store.set(_hb_key(0, 0), repr(now).encode())  # rank 0: fresh
    store.set(_hb_key(0, 1), repr(now - 120.0).encode())  # rank 1: stale
    det = FailureDetector(store, ranks=[0, 1, 2], incarnation=0,
                          timeout_s=60.0)
    assert det.stale_ranks(alive={0, 1, 2}) == [1]
    assert det.missed_counts[1] == 1 and det.missed_counts[0] == 0
    ages = det.last_beat_ages()
    assert ages[0] == pytest.approx(0.0, abs=5.0)
    assert ages[1] == pytest.approx(120.0, abs=5.0)
    assert ages[2] is None  # never beat
    runtime_gauges.export_detector_gauges(det, registry)
    snap = registry.snapshot()
    assert snap['worker_heartbeat_age_seconds{rank="2"}'] == -1.0
    assert snap['worker_missed_beats_total{rank="1"}'] == 1


def test_restart_gauges(registry):
    from pytorch_distributed_nn_tpu.obs import runtime_gauges

    assert "agent_restarts_total" not in registry.snapshot()
    runtime_gauges.export_restart_gauges(
        incarnations=3, restarts=2, preempt_restarts=1,
        backoff_seconds_total=3.5, last_exit_code=43, registry=registry)
    snap = registry.snapshot()
    assert snap["agent_incarnations_total"] == 3.0
    assert snap["agent_restarts_total"] == 2.0
    assert snap["agent_preempt_restarts_total"] == 1.0
    assert snap["agent_backoff_seconds_total"] == 3.5
    assert snap["agent_last_exit_code"] == 43.0


def test_cross_host_aggregation(registry):
    store = _FakeStore()
    registry.counter("train_steps_total").inc(10)
    registry.gauge("heartbeat_age_seconds").set(0.5)
    key = aggregate.publish_snapshot(store, rank=0, incarnation=0,
                                     registry=registry)
    assert key == "obs/0/0"
    # second host with its own registry
    other = reg_mod.MetricRegistry()
    other.counter("train_steps_total").inc(32)
    other.gauge("heartbeat_age_seconds").set(2.0)
    aggregate.publish_snapshot(store, rank=1, incarnation=0,
                               registry=other)
    snaps = aggregate.collect_snapshots(store, ranks=[0, 1, 2])
    assert set(snaps) == {0, 1}  # rank 2 never published: skipped
    merged = aggregate.merge_snapshots(snaps)
    assert merged["summed"]["train_steps_total"] == 42
    assert merged["per_rank"]["heartbeat_age_seconds"] == {0: 0.5,
                                                           1: 2.0}
    assert merged["hosts"] == 2


def test_maybe_publish_noop_outside_agent(registry):
    # no elastic agent in tests: must be a clean no-op, never a raise
    assert aggregate.maybe_publish(registry) is False


# ---------------------------------------------------------------------------
# acceptance: 2×2 training run end to end
# ---------------------------------------------------------------------------

@pytest.fixture()
def trained_run(registry, tmp_path):
    """One small mlp training run on a 2×2 (data×fsdp) mesh of 4 fake
    CPU devices, with JSONL metrics + Prometheus exposition + checkpoint
    cadence — shared by the acceptance assertions below."""
    import jax

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    jsonl = tmp_path / "metrics.jsonl"
    prom = tmp_path / "prom.txt"
    cfg = get_config("mlp_mnist", steps=8, log_every=2)
    cfg.data.prefetch = 0
    cfg.metrics_path = str(jsonl)
    cfg.prom_path = str(prom)
    cfg.eval_every = 4
    cfg.eval_batches = 1
    cfg.checkpoint_dir = str(tmp_path / "ckpt")
    cfg.checkpoint_every = 4
    mesh = make_mesh(MeshSpec(data=2, fsdp=2).resolve(4),
                     devices=jax.devices()[:4])
    with Trainer(cfg, mesh=mesh) as trainer:
        trainer.train()
    events = [json.loads(line)
              for line in jsonl.read_text().splitlines()]
    return {"events": events, "prom": prom, "jsonl": jsonl,
            "trainer": trainer}


def test_training_goodput_accounts_for_wall_time(trained_run):
    goodput = [e for e in trained_run["events"]
               if e["event"] == "goodput"]
    assert goodput, "trainer emitted no goodput events"
    measured_phases = [p for p in PHASES if p != "other"]
    for e in goodput:
        total = sum(e[f"{p}_s"] for p in PHASES)
        # data+compute+collective+checkpoint+eval+other vs wall: the
        # acceptance bound is >=95%; by construction it's ~100%
        assert total == pytest.approx(e["wall_s"], rel=0.05)
        # and "other" is genuinely residual, not a dumping ground
        assert e["accounted_frac"] >= 0.5
        assert sum(e[f"{p}_s"] for p in measured_phases) > 0
    summary = [e for e in trained_run["events"]
               if e["event"] == "goodput_summary"]
    assert len(summary) == 1
    s = summary[0]
    assert s["steps"] == 8
    assert s["accounted_frac"] >= 0.95
    assert s["checkpoint_s"] > 0  # checkpoint cadence hit
    assert s["eval_s"] > 0
    assert s["goodput_frac"] > 0


def test_training_prometheus_exposition(trained_run):
    text = trained_run["prom"].read_text()
    _assert_valid_prometheus(text)
    assert "train_steps_total 8" in text
    assert 'mesh_axis_size{axis="data"} 2' in text
    assert 'mesh_axis_size{axis="fsdp"} 2' in text
    assert "# TYPE train_step_seconds histogram" in text
    assert "data_batches_total" in text
    assert "checkpoint_saves_total" in text
    assert "goodput_frac" in text


def test_obs_report_renders_tables(trained_run, capsys):
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "obs_report",
        pathlib.Path(__file__).parent.parent / "scripts" / "obs_report.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main([str(trained_run["jsonl"]), "--last", "3"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "goodput breakdown" in out
    for p in PHASES:
        assert p in out
    assert "whole run" in out
    assert "train tail" in out
    assert "eval tail" in out


def test_trainer_spans_cover_the_stack(registry, tmp_path):
    """Span tracing through a real (tiny) run: data/checkpoint spans
    land in one Chrome trace with goodput phase spans."""
    import jax

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    cfg = get_config("mlp_mnist", steps=2, log_every=1)
    cfg.data.prefetch = 0
    cfg.checkpoint_dir = str(tmp_path / "ckpt")
    cfg.checkpoint_every = 2
    mesh = make_mesh(MeshSpec(data=2, fsdp=2).resolve(4),
                     devices=jax.devices()[:4])
    rec = obs.enable_tracing(process_index=0)
    try:
        with Trainer(cfg, mesh=mesh) as trainer:
            trainer.train()
    finally:
        obs.disable_tracing()
    names = {e["name"] for e in rec.events()}
    assert "data/host_batch" in names
    assert "checkpoint/save" in names
    assert "checkpoint/drain" in names
    assert "goodput/data" in names
    assert "goodput/compute" in names
    assert "goodput/checkpoint" in names
