"""Chaos engine (ISSUE 3 tentpole): spec grammar, determinism,
inertness, per-fault flight-ring visibility, and the corrupt-checkpoint
→ restore-fallback path."""

import os
import time

import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.runtime import chaos, failure


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Each test gets a disarmed engine, a fresh ring + registry, and a
    guaranteed-unset TPUNN_CHAOS env."""
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    monkeypatch.delenv(chaos.ENV_CHAOS_SEED, raising=False)
    chaos.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    yield
    chaos.reset()


def _chaos_ring_events():
    return [e for e in flight.get_recorder().snapshot()
            if e["kind"] == "chaos"]


# ---------------------------------------------------------------------------
# Spec grammar
# ---------------------------------------------------------------------------

def test_parse_full_grammar():
    faults = chaos.parse_spec(
        "crash@step=7:rank=1:inc=0;"
        "hang@collective=all_reduce:step=5:ms=50;"
        "slow@rank=2:ms=200;"
        "preempt@step=9;"
        "corrupt_ckpt@step=6;"
        "store_flaky@p=0.1;"
        "serve_reject@p=0.3;"
        "kill_replica@replica=1:after_s=2;"
        "hang_replica@replica=0:ms=50:step=3"
    )
    kinds = [f.kind for f in faults]
    assert kinds == ["crash", "hang", "slow", "preempt", "corrupt_ckpt",
                     "store_flaky", "serve_reject",
                     "kill_replica", "hang_replica"]
    assert faults[0].step == 7 and faults[0].rank == 1
    assert faults[0].inc == 0
    assert faults[1].collective == "all_reduce" and faults[1].ms == 50.0
    assert faults[2].ms == 200.0 and faults[2].rank == 2
    assert faults[5].p == 0.1
    assert faults[6].p == 0.3
    assert faults[7].replica == 1 and faults[7].after_s == 2.0
    assert faults[8].replica == 0 and faults[8].ms == 50.0
    assert faults[8].step == 3


@pytest.mark.parametrize("bad", [
    "boom@step=1",          # unknown fault
    "crash",                # missing required step=
    "hang@step=5",          # missing required collective=
    "slow@rank=1",          # missing required ms=
    "store_flaky",          # missing required p=
    "crash@step=x",         # bad int
    "crash@foo=1",          # unknown key
    "crash@step",           # not key=value
    "store_flaky@p=1.5",    # p out of range
    "serve_reject",         # missing required p=
    "serve_reject@p=2",     # p out of range
    "serve_reject@step=1",  # step alone doesn't satisfy required p=
    "kill_replica",         # missing required replica=
    "kill_replica@after_s=1",   # after_s alone doesn't satisfy replica=
    "hang_replica@ms=5",    # missing required replica=
    "kill_replica@replica=x",   # bad int
    "",                     # empty
])
def test_parse_rejects_bad_specs(bad):
    with pytest.raises(ValueError):
        chaos.parse_spec(bad)


# ---------------------------------------------------------------------------
# Inert when unset (the hot-path contract the quality lint also enforces)
# ---------------------------------------------------------------------------

def test_hooks_are_noops_when_unset():
    assert chaos.maybe_init() is None
    assert not chaos.enabled()
    chaos.on_step(1)
    chaos.on_collective("all_reduce")
    chaos.on_checkpoint_saved(None, 1)
    chaos.on_store_op("set", "k")
    chaos.on_replica_round(0, 1)
    assert _chaos_ring_events() == []
    assert chaos.engine() is None


def test_disabled_hook_overhead_is_negligible():
    """The disabled fast path is one global
    load + one comparison — 1M calls must stay far under any step
    budget (generous bound for loaded CI hosts)."""
    t0 = time.perf_counter()
    for i in range(1_000_000):
        chaos.on_step(i)
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"1M disabled chaos hooks took {dt:.2f}s"


def test_maybe_init_from_env(monkeypatch):
    monkeypatch.setenv(chaos.ENV_CHAOS, "slow@rank=0:ms=1")
    monkeypatch.setenv("RANK", "0")
    eng = chaos.maybe_init()
    assert eng is not None and chaos.enabled()
    assert chaos.maybe_init() is eng  # idempotent


# ---------------------------------------------------------------------------
# Fault behavior + flight-ring visibility (one test per fault kind)
# ---------------------------------------------------------------------------

def test_crash_fires_once_at_step_and_rank(monkeypatch):
    exits = []
    monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
    eng = chaos.ChaosEngine(chaos.parse_spec("crash@step=3:rank=0:inc=0"),
                            rank=0, incarnation=0)
    eng.step(1)
    eng.step(2)
    assert exits == []
    eng.step(3)
    assert exits == [chaos.CRASH_EXIT_CODE]
    eng.step(3)  # fire-once
    assert exits == [chaos.CRASH_EXIT_CODE]
    evs = _chaos_ring_events()
    assert len(evs) == 1 and evs[0]["op"] == "crash"
    assert evs[0]["step"] == 3


def test_crash_filters_rank_and_incarnation(monkeypatch):
    monkeypatch.setattr(os, "_exit",
                        lambda code: (_ for _ in ()).throw(SystemExit))
    # wrong rank
    chaos.ChaosEngine(chaos.parse_spec("crash@step=1:rank=1"),
                      rank=0).step(1)
    # wrong incarnation
    chaos.ChaosEngine(chaos.parse_spec("crash@step=1:inc=0"),
                      rank=0, incarnation=1).step(1)
    assert _chaos_ring_events() == []


def test_hang_sleeps_inside_collective_hook(monkeypatch):
    naps = []
    monkeypatch.setattr(time, "sleep", lambda s: naps.append(s))
    eng = chaos.ChaosEngine(
        chaos.parse_spec("hang@collective=all_reduce:step=5:ms=250"),
        rank=0)
    eng.step(4)
    eng.collective("all_reduce")  # wrong step
    eng.collective("ppermute")    # wrong op
    assert naps == []
    eng.step(5)
    eng.collective("all_reduce")
    assert naps == [0.25]
    eng.collective("all_reduce")  # fire-once
    assert naps == [0.25]
    evs = _chaos_ring_events()
    assert len(evs) == 1 and evs[0]["op"] == "hang"


def test_hang_default_duration_is_effectively_forever(monkeypatch):
    naps = []
    monkeypatch.setattr(time, "sleep", lambda s: naps.append(s))
    eng = chaos.ChaosEngine(chaos.parse_spec("hang@collective=psum"),
                            rank=0)
    eng.collective("psum")
    assert naps == [chaos.DEFAULT_HANG_MS / 1000.0]


def test_slow_fires_every_matching_step(monkeypatch):
    naps = []
    monkeypatch.setattr(time, "sleep", lambda s: naps.append(s))
    eng = chaos.ChaosEngine(chaos.parse_spec("slow@rank=2:ms=200"),
                            rank=2)
    for s in range(1, 4):
        eng.step(s)
    assert naps == [0.2, 0.2, 0.2]
    assert len(_chaos_ring_events()) == 3


def test_preempt_sends_sigterm_to_self(monkeypatch):
    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append(
        (pid, sig)))
    eng = chaos.ChaosEngine(chaos.parse_spec("preempt@step=9"), rank=0)
    eng.step(8)
    assert kills == []
    eng.step(9)
    import signal as _signal

    assert kills == [(os.getpid(), _signal.SIGTERM)]
    evs = _chaos_ring_events()
    assert len(evs) == 1 and evs[0]["op"] == "preempt"


def test_store_flaky_deterministic_and_visible():
    def sequence():
        chaos.reset()
        eng = chaos.ChaosEngine(chaos.parse_spec("store_flaky@p=0.4"),
                                rank=1, seed=11)
        out = []
        for i in range(30):
            try:
                eng.store_op("set", f"k{i}")
                out.append(0)
            except OSError:
                out.append(1)
        return out

    a, b = sequence(), sequence()
    assert a == b, "seeded store_flaky must replay identically"
    assert 0 < sum(a) < 30, a
    # a different rank draws a different (still deterministic) stream
    eng = chaos.ChaosEngine(chaos.parse_spec("store_flaky@p=0.4"),
                            rank=2, seed=11)
    c = []
    for i in range(30):
        try:
            eng.store_op("set", f"k{i}")
            c.append(0)
        except OSError:
            c.append(1)
    assert c != a
    assert len(_chaos_ring_events()) > 0


def test_store_flaky_through_real_store_client(monkeypatch):
    from pytorch_distributed_nn_tpu.runtime import native

    if not native.available():
        pytest.skip("native store not built")
    # p=1: every op through the REAL StoreClient hook must fail
    monkeypatch.setenv(chaos.ENV_CHAOS, "store_flaky@p=1.0")
    chaos.maybe_init(rank=0)
    with native.StoreServer() as server:
        client = native.StoreClient("127.0.0.1", server.port)
        with pytest.raises(OSError, match="chaos"):
            client.set("k", b"v")
        with pytest.raises(OSError, match="chaos"):
            client.get("k", timeout_ms=100)
        with pytest.raises(OSError, match="chaos"):
            client.check("k")
        chaos.reset()  # disarm: the raw path must work again
        client.set("k", b"v")
        assert client.get("k") == b"v"
        client.close()


def test_corrupt_ckpt_then_restore_falls_back(tmp_path):
    """Acceptance: chaos corrupts the latest kept step; restore falls
    back to the previous good step and bumps the fallback counter."""
    import jax

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.train.checkpoint import (
        CheckpointManager,
    )
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    cfg = get_config("mlp_mnist", steps=6, log_every=0)
    cfg.data.batch_size = 32
    cfg.data.prefetch = 0
    cfg.checkpoint_dir = str(tmp_path)
    cfg.checkpoint_every = 2
    with Trainer(cfg) as t:
        t.train()
        t.ckpt.wait()
        assert t.ckpt.all_steps() == [2, 4, 6]
        # the chaos engine tears the just-saved latest step
        eng = chaos.ChaosEngine(chaos.parse_spec("corrupt_ckpt@step=6"),
                                rank=0)
        eng.checkpoint_saved(t.ckpt, 6)
        evs = _chaos_ring_events()
        assert len(evs) == 1 and evs[0]["op"] == "corrupt_ckpt"

        mgr = CheckpointManager(tmp_path)
        state, meta = mgr.restore(t.state)
        assert meta["step"] == 4  # fell back past the torn step 6
        assert int(jax.device_get(state.step)) == 4
        counter = obs.get_registry().counter(
            "checkpoint_restore_fallbacks_total")
        assert counter.value() >= 1
        # flight ring saw the fallback too
        fb = [e for e in flight.get_recorder().snapshot()
              if e["kind"] == "checkpoint"
              and e["op"] == "restore_fallback"]
        assert fb and fb[0]["step"] == 6
        # an EXPLICITLY requested torn step still raises
        with pytest.raises(Exception):
            mgr.restore(t.state, step=6)
        mgr.close()


def test_corrupt_ckpt_rank_filter(tmp_path):
    eng = chaos.ChaosEngine(
        chaos.parse_spec("corrupt_ckpt@step=2:rank=1"), rank=0)

    class _Mgr:
        directory = tmp_path

        def wait(self):
            raise AssertionError("must not wait on a non-matching rank")

    eng.checkpoint_saved(_Mgr(), 2)  # no-op: rank filter
    assert _chaos_ring_events() == []


# ---------------------------------------------------------------------------
# Trainer wiring: in-process preemption (SIGTERM-free via the flag API)
# ---------------------------------------------------------------------------

def test_trainer_graceful_preempt_saves_and_exits(tmp_path, monkeypatch):
    """The worker half of the preemption contract, in-process: the
    preempt flag arrives mid-run → the loop finishes its step, forces a
    synchronous save, and raises SystemExit(GRACEFUL_EXIT_CODE)."""
    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    monkeypatch.setenv(failure.ENV_PREEMPT, "1")
    cfg = get_config("mlp_mnist", steps=50, log_every=0)
    cfg.data.batch_size = 32
    cfg.data.prefetch = 0
    cfg.checkpoint_dir = str(tmp_path)
    cfg.checkpoint_every = 0  # only the preemption save writes
    trainer = Trainer(cfg)
    try:
        assert trainer._preemptible

        real_on_step = chaos.on_step

        def notice_at_step_3(step):
            real_on_step(step)
            if step == 3:
                failure.request_preemption()

        monkeypatch.setattr(chaos, "on_step", notice_at_step_3)
        with pytest.raises(SystemExit) as exc:
            trainer.train()
        assert exc.value.code == failure.GRACEFUL_EXIT_CODE
        # the forced synchronous save landed at the preempted step
        assert trainer.ckpt.all_steps() == [3]
        assert trainer.data_step == 3
        counter = obs.get_registry().counter("preempt_exits_total")
        assert counter.value() == 1
        pre = [e for e in flight.get_recorder().snapshot()
               if e["kind"] == "preempt"]
        assert pre and pre[-1]["op"] == "graceful_exit"
    finally:
        trainer.close()
    # handler restored on close
    assert not failure.preempt_requested()


# ---------------------------------------------------------------------------
# Replica faults (ISSUE 8): the fleet driver hook
# ---------------------------------------------------------------------------

def test_kill_replica_fires_once_on_matching_replica_and_round():
    eng = chaos.ChaosEngine(
        chaos.parse_spec("kill_replica@replica=1:step=2"), rank=0)
    eng.replica_round(0, 2)  # wrong replica: inert
    eng.replica_round(1, 1)  # wrong round: inert
    assert _chaos_ring_events() == []
    with pytest.raises(chaos.ReplicaKillError):
        eng.replica_round(1, 2)
    eng.replica_round(1, 2)  # fire-once: a second pass is inert
    events = _chaos_ring_events()
    assert len(events) == 1 and events[0]["op"] == "kill_replica"
    assert "replica 1" in events[0]["note"]
    counter = obs.get_registry().counter("chaos_injected_total")
    assert counter.value(kind="kill_replica") == 1


def test_kill_replica_after_s_gates_on_wall_clock():
    eng = chaos.ChaosEngine(
        chaos.parse_spec("kill_replica@replica=0:after_s=30"), rank=0)
    eng.replica_round(0, 1)  # armed 30s not elapsed yet: inert
    assert _chaos_ring_events() == []
    eng._t0 -= 31.0  # pretend the engine armed 31s ago
    with pytest.raises(chaos.ReplicaKillError):
        eng.replica_round(0, 2)


def test_hang_replica_sleeps_and_emits_first():
    eng = chaos.ChaosEngine(
        chaos.parse_spec("hang_replica@replica=0:ms=30"), rank=0)
    t0 = time.perf_counter()
    eng.replica_round(0, 1)  # blocks ~30ms, then returns
    assert time.perf_counter() - t0 >= 0.02
    events = _chaos_ring_events()
    assert len(events) == 1 and events[0]["op"] == "hang_replica"
    eng.replica_round(0, 2)  # fire-once
    assert len(_chaos_ring_events()) == 1


# ---------------------------------------------------------------------------
# kill_coordinator / store_partition (ISSUE 13: process-fleet faults)
# ---------------------------------------------------------------------------


def test_kill_coordinator_fires_once_after_fuse():
    eng = chaos.ChaosEngine(
        chaos.parse_spec("kill_coordinator@after_s=30"), rank=0)
    eng.coordinator_poll()  # fuse not burned: inert
    assert _chaos_ring_events() == []
    eng._t0 -= 31.0  # pretend the engine armed 31s ago
    with pytest.raises(chaos.CoordinatorKillError):
        eng.coordinator_poll()
    events = _chaos_ring_events()
    assert len(events) == 1 and events[0]["op"] == "kill_coordinator"
    eng.coordinator_poll()  # fire-once: the successor polls in peace
    assert len(_chaos_ring_events()) == 1
    counter = obs.get_registry().counter("chaos_injected_total")
    assert counter.value(kind="kill_coordinator") == 1


def test_kill_coordinator_requires_after_s():
    with pytest.raises(ValueError):
        chaos.parse_spec("kill_coordinator")


def test_store_partition_window_opens_and_closes():
    eng = chaos.ChaosEngine(
        chaos.parse_spec("store_partition@ms=40"), rank=0)
    # the window opens on the FIRST eligible op; every op inside the
    # window raises, ops after it succeed again
    with pytest.raises(OSError):
        eng.store_op("set", "hb/0/0")
    with pytest.raises(OSError):
        eng.store_op("get", "gauge/1")
    time.sleep(0.06)
    eng.store_op("set", "hb/0/0")  # window closed: store is back
    events = _chaos_ring_events()
    assert all(e["op"] == "store_partition" for e in events)
    assert len(events) == 2


def test_store_partition_rank_filter_and_after_s():
    # rank filter: this engine is rank 0, the fault targets rank 1
    eng = chaos.ChaosEngine(
        chaos.parse_spec("store_partition@rank=1:ms=40"), rank=0)
    eng.store_op("set", "k")  # not our rank: inert
    assert _chaos_ring_events() == []
    # after_s gates the window opening on wall time since arm
    eng2 = chaos.ChaosEngine(
        chaos.parse_spec("store_partition@ms=40:after_s=30"), rank=0)
    eng2.store_op("set", "k")  # fuse not burned: inert
    assert _chaos_ring_events() == []
    eng2._t0 -= 31.0
    with pytest.raises(OSError):
        eng2.store_op("set", "k")


def test_store_partition_requires_ms():
    with pytest.raises(ValueError):
        chaos.parse_spec("store_partition")
