"""Process-backed fleet (serve/procfleet.py).

Cheap tests cover the pieces that need no subprocess: ticket
semantics, the gauge duck-types the UNMODIFIED Router scores, and
constructor validation. The drills run stub workers
(serve/fleet_worker.py subprocesses) over a live native store. Tier-1
runs the two acceptance drills: the coordinator kill with Helm's
journal carried across it, and the fault-tolerant KV wire (torn
chunks, a kvwire-scoped partition, a source killed inside the push, a
coordinator death mid-handoff). The plainer ones — worker kill +
stitched re-admission, abandon + adoption, trace continuity — are
``slow``.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from pytorch_distributed_nn_tpu.obs import flight, forensics
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import autoscale
from pytorch_distributed_nn_tpu.serve.procfleet import (
    ProcReplica,
    ProcTicket,
    ProcessFleet,
)
from pytorch_distributed_nn_tpu.serve.router import (
    DRAINING,
    READY,
    STARTING,
    Router,
)
from pytorch_distributed_nn_tpu.serve.stub import stub_decode


@pytest.fixture(autouse=True)
def _no_chaos():
    chaos.reset()
    yield
    chaos.reset()


@pytest.fixture
def fleets():
    """The fleets a drill builds, stopped pass or fail, newest first:
    a successor's ``stop`` ends the workers it adopted, then the dead
    coordinator's closes the store it hosts. No ``fleet_worker``
    outlives its case."""
    made = []
    yield made
    for fleet in reversed(made):
        fleet.stop()


# -- no-subprocess units ---------------------------------------------------


def test_ticket_lifecycle():
    t = ProcTicket("r0", [3, 1, 4], 8)
    assert t.prompt == [3, 1, 4] and t.max_new_tokens == 8
    assert t.status == "pending" and not t.ok
    assert t.ttft_s == -1.0  # no first token yet
    assert t.result(timeout=0.01) is None  # pending -> no tokens
    t.t_first_token = t.t_submit + 0.5
    assert abs(t.ttft_s - 0.5) < 1e-9
    t.tokens = np.array([7, 7], dtype=np.int32)
    t.status = "done"
    t.done.set()
    assert t.ok and list(t.result()) == [7, 7]


def _handle(index: int, *, state: str, queue_depth: int = 0,
            free_blocks: int = 4) -> ProcReplica:
    h = ProcReplica(index, policy=None, max_queue=8, max_slots=4)
    h.state = state
    h.engine.scheduler.queue_depth = queue_depth
    h.engine.scheduler.pool.free_blocks = free_blocks
    return h


def test_router_scores_remote_gauges():
    """The gauge duck-types (_RemoteEngine et al.) satisfy the exact
    surface Router._score reads, so the unmodified thread-fleet router
    places process-fleet requests too."""
    idle = _handle(0, state=READY, queue_depth=0)
    busy = _handle(1, state=READY, queue_depth=8)
    r = Router()
    assert r.place([busy, idle], total_tokens=2) is idle
    # non-READY replicas are never candidates
    assert r.place([_handle(0, state=STARTING),
                    _handle(1, state=DRAINING)], total_tokens=2) is None


def test_constructor_validation():
    with pytest.raises(ValueError, match="replicas"):
        ProcessFleet(replicas=0)
    # workers are subprocesses: an in-process MemStore can't reach them
    with pytest.raises(ValueError, match="mem"):
        ProcessFleet(store_endpoint="mem")


# -- subprocess drills (each spawns real interpreters) ---------------------


def _prompts(n):
    return [[31 + i, 7, 2] for i in range(n)]


def test_kill_coordinator_drill(fleets, tmp_path):
    """The coordinator crash-recovery drill:

    1. a chaos ``kill_coordinator`` leaves the workers serving;
    2. the successor adopts them pid-for-pid — no cold restart;
    3. every in-flight request finishes bit-identical to the stub
       reference (stitched across the gap, zero duplicate tokens);
    4. Helm's journal CONTINUES across the boundary — seq contiguous,
       state chained through the deterministic policy (so the
       successor converges to the same replicas_needed), the
       ``coordinator_incarnation`` field marking where it fell — and
       the concatenated journal shadow-replays clean through
       ``scripts/obs_watch.py --autoscale``;
    5. obs forensics names the supervision gap."""
    flight.reset_recorder(enabled=True)
    # min_replicas=2: on a loaded host one worker joins seconds before
    # the other, and Helm, seeing headroom, would retire it again
    # before the drill ever has its two
    spec = ("eval_interval_s=0.1:up_consecutive=2:cooldown_up_s=0.3:"
            "min_replicas=2:max_replicas=3:queue_up=0.25")
    f1 = ProcessFleet(replicas=2, backend="stub",
                      heartbeat_interval_s=0.05,
                      heartbeat_timeout_s=2.0, token_ms=6.0,
                      autoscale_spec=spec)
    fleets.append(f1)
    f1.start()
    assert f1.wait_ready(2, timeout=120), "workers never joined"
    prompts = _prompts(10)
    tickets = [f1.submit(p, 64) for p in prompts]
    deadline = time.monotonic() + 30
    while len(f1.helm_journal) == 0 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert len(f1.helm_journal) > 0, "no pre-kill Helm decision"
    # kill the coordinator mid-flash-crowd (armed only now, so the
    # workers' multi-second join can't outrun the fuse)
    chaos.maybe_init("kill_coordinator@after_s=0.05", rank=0, seed=0)
    deadline = time.monotonic() + 30
    while not f1.dead and time.monotonic() < deadline:
        time.sleep(0.02)
    assert f1.dead, "chaos kill_coordinator never fired"
    pids = {h.index: h.pid for h in f1.replicas
            if h.state in ("ready", "draining")}
    helm_pre = len(f1.helm_journal)
    time.sleep(0.8)  # the unsupervised gap: workers keep decoding

    f2 = ProcessFleet.recover_from(
        store_endpoint=f1.store_endpoint,
        heartbeat_interval_s=0.05, heartbeat_timeout_s=2.0,
        token_ms=6.0, autoscale_spec=spec)
    fleets.append(f2)
    assert f2.incarnation == f1.incarnation + 1, \
        (f1.incarnation, f2.incarnation)
    assert f2.gap_s > 0, "no supervision gap measured"
    adopted = {h.index: h.pid for h in f2.replicas if h.adopted}
    assert adopted and all(pids.get(i) == p
                           for i, p in adopted.items()), \
        f"adoption restarted live workers: {pids} -> {adopted}"
    f2.start()
    assert f2.wait_all(list(f2.recovered_tickets.values()),
                       timeout=120), "recovered requests never finished"
    for p, t0 in zip(prompts, tickets):
        t = f2.recovered_tickets[t0.request_id]
        got = list(t.tokens) if t.tokens is not None else None
        assert got == stub_decode(p, 64), \
            f"stitched output diverged for {t.request_id}"
        assert len(got) == 64, \
            f"duplicate/missing tokens for {t.request_id}: {len(got)}"

    deadline = time.monotonic() + 30
    while (len(f2.helm_journal) <= helm_pre
           and time.monotonic() < deadline):
        time.sleep(0.05)
    lines = f2.helm_journal.read_lines()
    recs = [json.loads(ln) for ln in lines]
    assert len(recs) > helm_pre, "recovered Helm never journaled"
    assert [r["seq"] for r in recs] == list(range(len(recs))), \
        "journal seq forked across the restart"
    incs = [r["coordinator_incarnation"] for r in recs]
    assert incs == sorted(incs) and \
        sorted(set(incs)) == [f1.incarnation, f2.incarnation], incs
    boundary = incs.index(f2.incarnation)
    pre, post = recs[boundary - 1], recs[boundary]
    _, _, _, want_state = autoscale.decide(
        autoscale.parse_spec(pre["spec"]), pre["evidence"],
        pre["state"], float(pre["t"]))
    assert post["state"] == want_state, \
        "successor's first decision does not chain off the " \
        "predecessor's post-state"

    jpath = tmp_path / "helm.jsonl"
    jpath.write_text("\n".join(lines) + "\n")
    repo = Path(__file__).parent.parent
    watch = subprocess.run(
        [sys.executable, str(repo / "scripts" / "obs_watch.py"),
         str(jpath), "--autoscale"],
        capture_output=True, text=True, timeout=300, cwd=repo)
    assert watch.returncode == 0, \
        f"obs_watch --autoscale rejected the concatenated " \
        f"journal:\n{watch.stdout}\n{watch.stderr}"

    att = forensics.attribute(flight.get_recorder().snapshot())
    assert att.get("coordinator_gap_s", 0.0) > 0, \
        f"forensics did not name the coordinator gap: {att}"


# The fault-tolerant KV wire: stub prefill/decode pools, the handoff
# streamed through serve/kv_wire.py, one request unless said otherwise.

_BUDGET = 32


def _golden(n):
    return [stub_decode(p, _BUDGET) for p in _prompts(n)]


def _steady(outs, pulls, pump, failovers):
    # warm wire, the transfer pump overlapping the poll loop
    assert outs == _golden(3), \
        f"disagg output diverged:\n{outs}\n{_golden(3)}"
    assert len(pulls) == 3 and all(
        p["outcome"] == "warm" for p in pulls), pulls
    assert pump > 0, "transfer pump emitted no flight events"


def _torn_once(outs, pulls, pump, failovers):
    # one bounded re-pull, still warm
    assert outs == _golden(1), f"re-pull broke bit-identity: {outs}"
    assert pulls and pulls[0]["outcome"] == "warm", pulls


def _torn_always(outs, pulls, pump, failovers):
    # re-pulls exhaust: a graceful cold re-prefill, never a wedge
    assert outs == _golden(1), f"cold path broke bit-identity: {outs}"
    assert pulls and pulls[0]["outcome"] == "cold", pulls


def _partition(outs, pulls, pump, failovers):
    # counted retries ride it out; replica health (heartbeats, done
    # polls) never notices
    assert outs == _golden(1), f"partition broke bit-identity: {outs}"
    assert failovers == 0, \
        f"transfer-window partition leaked into replica health: " \
        f"{failovers} failovers"


def _source_killed(outs, pulls, pump, failovers):
    # done already published: the decode leg re-prefills cold
    assert outs == _golden(1), \
        f"transfer kill broke bit-identity: {outs}"
    assert pulls and pulls[0]["outcome"] == "cold", pulls


@pytest.mark.parametrize("worker_chaos, n, check", [
    pytest.param("", 3, _steady, id="steady_pump_overlap"),
    pytest.param("corrupt_wire@seq=0", 1, _torn_once,
                 id="one_torn_chunk_repulls_warm"),
    pytest.param("corrupt_wire@p=1.0", 1, _torn_always,
                 id="every_repull_torn_goes_cold"),
    pytest.param("store_partition@ms=800:window=transfer", 1,
                 _partition, id="kvwire_partition_no_failover"),
    pytest.param("kill_transfer@step=1", 1, _source_killed,
                 id="source_killed_inside_push"),
])
def test_kv_wire_drill(fleets, worker_chaos, n, check):
    """Disaggregated output stays bit-identical to the stub reference
    under each wire fault; the decode WORKER writes its ``kv_pull``
    disposition into the coordinator's journal."""
    fleet = ProcessFleet(
        prefill=1, decode=1, backend="stub",
        heartbeat_interval_s=0.05, heartbeat_timeout_s=10.0,
        token_ms=2.0,
        worker_extra_env={"TPUNN_CHAOS": worker_chaos})
    fleets.append(fleet)
    fleet.start()
    assert fleet.wait_ready(2, timeout=120), "workers never joined"
    tickets = [fleet.submit(p, _BUDGET) for p in _prompts(n)]
    assert fleet.wait_all(tickets, timeout=120), \
        f"requests wedged under {worker_chaos or 'no chaos'!r}"
    outs = [list(t.tokens) for t in tickets]
    pulls = [r for r in fleet.journal.read_all()
             if r.get("event") == "kv_pull"]
    check(outs, pulls, fleet._pump.events, fleet.failovers)


def test_coordinator_death_mid_handoff_replays_from_journal(fleets):
    """The coordinator dies between handoff and final: the successor
    adopts the workers pid-for-pid, rediscovers the disaggregation
    from live roles, replays the handoff from the journal, and the
    stitched output is STILL bit-identical."""
    f1 = ProcessFleet(prefill=1, decode=1, backend="stub",
                      heartbeat_interval_s=0.05,
                      heartbeat_timeout_s=10.0, token_ms=6.0)
    fleets.append(f1)
    f1.start()
    assert f1.wait_ready(2, timeout=120), "workers never joined"
    t0 = f1.submit(_prompts(1)[0], _BUDGET)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not any(
            r.get("event") == "handoff" for r in f1.journal.read_all()):
        time.sleep(0.01)
    assert any(r.get("event") == "handoff"
               for r in f1.journal.read_all()), "handoff never journaled"
    pids = {h.index: h.pid for h in f1.replicas
            if h.state in ("ready", "draining")}
    f1.abandon()

    f2 = ProcessFleet.recover_from(
        store_endpoint=f1.store_endpoint,
        heartbeat_interval_s=0.05, heartbeat_timeout_s=10.0,
        token_ms=6.0)
    fleets.append(f2)
    assert f2.disagg, "successor lost the disaggregation"
    adopted = {h.index: h.pid for h in f2.replicas if h.adopted}
    assert adopted and all(pids.get(i) == p
                           for i, p in adopted.items()), \
        f"adoption restarted live workers: {pids} -> {adopted}"
    f2.start()
    assert f2.wait_all(list(f2.recovered_tickets.values()),
                       timeout=120), "handoff replay never finished"
    t = f2.recovered_tickets[t0.request_id]
    assert list(t.tokens) == _golden(1)[0], \
        "mid-handoff takeover broke bit-identity"


# -- the plainer drills (slow) ---------------------------------------------


@pytest.mark.slow
def test_e2e_stub_bit_identical():
    with ProcessFleet(replicas=2, backend="stub",
                      heartbeat_interval_s=0.05,
                      heartbeat_timeout_s=5.0) as fleet:
        fleet.start()
        assert fleet.wait_ready(2, timeout=120)
        tickets = [fleet.submit(p, 32) for p in _prompts(4)]
        assert fleet.wait_all(tickets, timeout=60)
        for p, t in zip(_prompts(4), tickets):
            assert t.ok and list(t.tokens) == stub_decode(p, 32)


@pytest.mark.slow
def test_worker_kill_failover_stitches():
    """kill_replica fires inside a worker subprocess mid-request; the
    coordinator re-admits the stranded work with its emitted prefix and
    greedy decode keeps the stitched stream bit-identical."""
    with ProcessFleet(
            replicas=2, backend="stub",
            heartbeat_interval_s=0.05, heartbeat_timeout_s=2.0,
            worker_extra_env={
                "TPUNN_CHAOS": "kill_replica@replica=1:step=30"},
    ) as fleet:
        fleet.start()
        assert fleet.wait_ready(2, timeout=120)
        tickets = [fleet.submit(p, 64) for p in _prompts(4)]
        assert fleet.wait_all(tickets, timeout=120)
        for p, t in zip(_prompts(4), tickets):
            assert t.ok and list(t.tokens) == stub_decode(p, 64)
        assert fleet.failovers >= 1


@pytest.mark.slow
def test_coordinator_abandon_adopt_readmit():
    """Coordinator replacement without a cold restart: the successor
    adopts still-beating workers pid-for-pid, re-admits what the
    journal says was stranded, and the stitched output stays
    bit-identical."""
    f1 = ProcessFleet(replicas=2, backend="stub", token_ms=10.0,
                      heartbeat_interval_s=0.05,
                      heartbeat_timeout_s=2.0)
    f2 = None
    try:
        f1.start()
        assert f1.wait_ready(2, timeout=120)
        for p in _prompts(4):
            f1.submit(p, 48)
        time.sleep(0.3)  # let some tokens land before the "crash"
        pids = sorted(h.pid for h in f1.replicas if h.proc)
        f1.abandon()  # supervision stops; worker processes live on
        assert f1.dead

        f2 = ProcessFleet.recover_from(
            store_endpoint=f1.store_endpoint,
            heartbeat_interval_s=0.05, heartbeat_timeout_s=2.0)
        assert f2.incarnation == f1.incarnation + 1
        adopted = sorted(h.pid for h in f2.replicas if h.adopted)
        assert adopted == pids  # adoption, not restart
        f2.start()
        assert f2.wait_all(f2.recovered_tickets.values(), timeout=120)
        for p, t in zip(_prompts(4),
                        f2.recovered_tickets.values()):
            assert t.ok and list(t.tokens) == stub_decode(p, 48)
    finally:
        if f2 is not None:
            f2.stop()
        f1._client.close()
        if f1._server is not None:
            f1._server.stop()


@pytest.mark.slow
def test_trace_context_survives_process_boundary():
    """Causeway cross-process continuity (ISSUE 16): the coordinator
    mints the context, ships it inside the ``req/<idx>/<k>`` dispatch
    record, and each worker SUBPROCESS emits its own decode span into
    its own buffer, published at ``trace/<idx>`` — pulled back through
    the store, the worker spans carry the coordinator's trace ids."""
    from pytorch_distributed_nn_tpu.obs import aggregate
    from pytorch_distributed_nn_tpu.obs import trace as tr

    tr.reset()
    tr.maybe_init("1", rank=0)
    try:
        with ProcessFleet(
                replicas=2, backend="stub",
                heartbeat_interval_s=0.05, heartbeat_timeout_s=5.0,
                worker_extra_env={"TPUNN_TRACE": "1"},
        ) as fleet:
            fleet.start()
            assert fleet.wait_ready(2, timeout=120)
            tickets = [fleet.submit(p, 16) for p in _prompts(3)]
            assert fleet.wait_all(tickets, timeout=60)
            minted = {t.trace.trace_id for t in tickets}
            assert len(minted) == 3  # every ticket carried a context
            deadline = time.time() + 30
            spans = []
            while time.time() < deadline:
                spans = aggregate.collect_spans(
                    fleet._ns, range(2))
                done = [s for s in spans
                        if s.get("segment") == "decode"
                        and s.get("status") == "done"]
                if {s["trace"] for s in done} >= minted:
                    break
                time.sleep(0.2)
        workers = [s for s in spans if s.get("segment") == "decode"]
        assert {s["trace"] for s in workers} >= minted, \
            (minted, workers)
        # the worker recovered the full context from the wire, not
        # just the id: leg + root span match what the coordinator sent
        by_id = {t.trace.trace_id: t.trace for t in tickets}
        for s in workers:
            if s["trace"] in by_id:
                ctx = by_id[s["trace"]]
                assert s["span"] == ctx.span_id
                assert s["leg"] == ctx.leg
                assert s["host"] in ("h0", "h1")
    finally:
        tr.reset()
