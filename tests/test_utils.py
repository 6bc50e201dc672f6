"""Profiling/metrics utilities: timer fencing, bus-bw accounting math,
JSONL metric schema (SURVEY.md §5 tracing + metrics rows)."""

import json

import jax
import pytest
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from pytorch_distributed_nn_tpu.ops import collectives as cc
from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
from pytorch_distributed_nn_tpu.utils.metrics import MetricsLogger
from pytorch_distributed_nn_tpu.utils.profiling import (
    StepTimer,
    bus_bandwidth,
    time_steps,
)


def test_step_timer_summary():
    t = StepTimer()
    for _ in range(5):
        t.start()
        t.stop(jnp.ones(8))
    s = t.summary()
    assert s["steps"] == 5
    assert s["total_s"] >= s["p50_s"]


def test_step_timer_empty_summary():
    """Regression: summary() on an empty timer used to crash in
    np.percentile([], 50); it must return a zeroed summary instead."""
    s = StepTimer().summary()
    assert s == {"steps": 0, "mean_s": 0.0, "p50_s": 0.0,
                 "p95_s": 0.0, "total_s": 0.0}


def test_time_steps_carries_state():
    calls = []

    def step(state, x):
        calls.append(int(state))
        return state + 1, x

    timer = time_steps(step, lambda i: (0, jnp.ones(2)), iters=4, warmup=2)
    assert len(timer.times) == 4
    # warmup carried: 0,1 then timed from 2
    assert calls[:3] == [0, 1, 2]


def test_bus_bandwidth_allreduce_accounting(mesh8):
    """all_reduce over 8 devices: wire bytes = 2(n-1)/n × payload."""
    x = jnp.ones((1024,), jnp.float32)  # 4096 B payload

    def f(x):
        return cc.all_reduce_sum(x, "data")

    with cc.recording() as records:
        jax.jit(jax.shard_map(
            f, mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        )).lower(jnp.ones((8 * 1024,)))
    bw = bus_bandwidth(records, step_s=1e-3)
    expected_wire = 2 * (8 - 1) / 8 * 4096
    assert bw.wire_bytes_per_step == expected_wire
    np.testing.assert_allclose(bw.wire_gbps, expected_wire / 1e-3 / 1e9)


def test_metrics_logger_context_manager(tmp_path):
    """MetricsLogger is a context manager: the file handle closes on
    exception exit (the Trainer leak the `with` form exists to stop),
    close() is idempotent, and emit-after-close is a silent no-op."""
    path = tmp_path / "metrics.jsonl"
    try:
        with MetricsLogger(path) as m:
            m.emit("step", loss=1.0)
            fh = m._fh
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert fh.closed
    m.close()  # second close: no-op, no error
    m.emit("after_close", x=1)  # no crash, nothing written
    lines = path.read_text().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["event"] == "step"


def test_metrics_logger_jsonl(tmp_path):
    path = tmp_path / "metrics.jsonl"
    m = MetricsLogger(path)
    m.emit("step", loss=1.5, step=3)
    m.close()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert lines[0]["event"] == "step" and lines[0]["loss"] == 1.5


def test_trainer_emits_metrics_jsonl(tmp_path):
    import json

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    path = tmp_path / "metrics.jsonl"
    cfg = get_config("mlp_mnist", steps=4, log_every=2)
    cfg.data.prefetch = 0
    cfg.metrics_path = str(path)
    cfg.eval_every = 4
    cfg.eval_batches = 1
    trainer = Trainer(cfg, mesh=make_mesh(MeshSpec(data=8).resolve(8)))
    trainer.train()
    trainer.close()
    events = [json.loads(line) for line in path.read_text().splitlines()]
    kinds = [e["event"] for e in events]
    assert "train_step" in kinds and "eval" in kinds
    step_ev = next(e for e in events if e["event"] == "train_step")
    assert {"step", "loss", "seconds", "samples_per_sec"} <= set(step_ev)
    eval_ev = next(e for e in events if e["event"] == "eval")
    assert {"step", "loss", "accuracy"} <= set(eval_ev)


@pytest.mark.slow  # real jax.profiler capture: seconds of trace I/O
def test_collective_trace_seconds(tmp_path, mesh8):
    """Profile-derived collective time (bench bus-bw cross-check): a
    profiled psum loop must yield collective slices whose summed
    duration is positive and attributed per device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_nn_tpu.utils.profiling import (
        collective_trace_seconds,
        xprof_trace,
    )

    @jax.jit
    def step(x):
        return jax.shard_map(
            lambda a: jax.lax.psum(a * 2.0, "data"),
            mesh=mesh8, in_specs=P("data"), out_specs=P(),
        )(x).sum()

    x = jnp.ones((8 * 256, 256), jnp.float32)
    float(step(x))  # compile outside the trace
    steps = 3
    with xprof_trace(str(tmp_path), perfetto=True):
        for _ in range(steps):
            v = step(x)
        jax.block_until_ready(v)
    ct = collective_trace_seconds(str(tmp_path), world=8)
    assert ct is not None, "no collective slices found"
    # one psum per device per step
    assert ct.n_events >= 8 * steps
    assert ct.total_s > 0
    assert ct.per_device_s == pytest.approx(ct.total_s / 8)
    assert all(v > 0 for v in ct.names.values())


def test_collective_trace_none_when_absent(tmp_path):
    from pytorch_distributed_nn_tpu.utils.profiling import (
        collective_trace_seconds,
    )

    assert collective_trace_seconds(str(tmp_path), world=8) is None


def _write_perfetto_fixture(tmp_path, events):
    import gzip

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "perfetto_trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return tmp_path


def test_collective_trace_slice_filtering(tmp_path):
    """Synthetic perfetto fixture (no profiler run): `$`-prefixed
    Python slices and paired `end:` markers are excluded, async
    start/done pairs both count, non-collective fusions are ignored,
    and only ph=X complete events contribute."""
    from pytorch_distributed_nn_tpu.utils.profiling import (
        collective_trace_seconds,
    )

    X = {"ph": "X", "ts": 0}
    events = [
        # counted: plain collective slices on two device tracks
        {**X, "name": "all-reduce.3", "dur": 100.0, "pid": 1},
        {**X, "name": "all-reduce.3", "dur": 100.0, "pid": 2},
        # counted: async pair — start covers transfer, done the wait
        {**X, "name": "all-reduce-start.1", "dur": 40.0, "pid": 1},
        {**X, "name": "all-reduce-done.1", "dur": 10.0, "pid": 1},
        # counted: XLA:CPU HLO spelling
        {**X, "name": "psum_invariant.7", "dur": 50.0, "pid": 1},
        # excluded: python-level slice, paired end marker, plain
        # fusion, non-X phase, zero-information metadata
        {**X, "name": "$train.py:42 step", "dur": 999.0, "pid": 1},
        {**X, "name": "end: all-reduce.3", "dur": 999.0, "pid": 1},
        {**X, "name": "fusion.1", "dur": 999.0, "pid": 1},
        {"ph": "M", "name": "all-reduce.metadata"},
        {"ph": "i", "name": "all-reduce.instant", "ts": 0},
    ]
    _write_perfetto_fixture(tmp_path, events)
    ct = collective_trace_seconds(str(tmp_path), world=2)
    assert ct is not None
    assert ct.n_events == 5
    assert ct.total_s == pytest.approx(300.0 / 1e6)
    assert ct.per_device_s == pytest.approx(150.0 / 1e6)
    assert ct.names["all-reduce.3"] == pytest.approx(200.0 / 1e6)
    assert "$train.py:42 step" not in ct.names
    assert "end: all-reduce.3" not in ct.names


def test_collective_trace_none_when_no_collectives(tmp_path):
    """A trace with only non-collective slices reports None (the
    world==1 case: XLA elides the collectives entirely)."""
    from pytorch_distributed_nn_tpu.utils.profiling import (
        collective_trace_seconds,
    )

    _write_perfetto_fixture(tmp_path, [
        {"ph": "X", "ts": 0, "name": "fusion.9", "dur": 10.0},
        {"ph": "X", "ts": 0, "name": "$loop.py:1 f", "dur": 10.0},
    ])
    assert collective_trace_seconds(str(tmp_path), world=1) is None
