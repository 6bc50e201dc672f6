"""Estuary (ISSUE 15): disaggregated prefill/decode fleet — two-stage
router placement, KV block streaming through the collectives choke
point, handoff bit-identity vs the unified fleet, and the
``kill_transfer@`` chaos drill (mid-transfer source death, re-prefill
on a survivor, output invariant)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.inference.generate import generate
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.ops import collectives
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import (
    DEAD,
    READY,
    Fleet,
    Router,
)
from pytorch_distributed_nn_tpu.serve.disagg import DisaggFleet

VOCAB = 97


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Disarmed chaos, fresh flight ring + metric registry per test."""
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    monkeypatch.delenv(chaos.ENV_CHAOS_SEED, raising=False)
    chaos.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    yield
    chaos.reset()


@pytest.fixture(scope="module")
def tiny_llama():
    model = get_model(ModelConfig(
        name="llama3_8b", compute_dtype="float32", dtype="float32",
        extra=dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   mlp_dim=128, vocab_size=VOCAB),
    ))
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.key(1), tokens, train=False)["params"]
    return model, params


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=(n,)).astype(np.int32)
            for n in lengths]


def _golden(model, params, prompt, n):
    return np.asarray(generate(model, params, prompt[None], n))[
        0, len(prompt):]


def _unified(model, params, prompts, budgets):
    """What ``Fleet(replicas=P+D)`` answers for the same workload: the
    other reference a disaggregated fleet is held to."""
    fleet = Fleet(model, params, replicas=3, max_slots=2,
                  max_seq_len=64, block_size=16)
    tickets = [fleet.submit(p, n) for p, n in zip(prompts, budgets)]
    fleet.run_until_idle()
    for t in tickets:
        assert t.ok, (t.status, t.reject_reason)
    return [list(t.tokens) for t in tickets]


def _fleet_ring(op=None):
    evs = [e for e in flight.get_recorder().snapshot()
           if e["kind"] == "fleet"]
    return [e for e in evs if e["op"] == op] if op else evs


# ---------------------------------------------------------------------------
# Two-stage router (no model needed: scored off scheduler/pool gauges)
# ---------------------------------------------------------------------------

def _handle(index, state, *, role=None, free_blocks=16, num_blocks=16,
            block_size=4, queue_depth=0, max_queue=8, peek=None):
    """A scoring stand-in. ``role=None`` mimics the unified fleet's
    pre-disagg handles (no attribute at all — place() must default it);
    ``peek`` installs a prefix cache whose ``peek`` returns that many
    resident tokens."""
    pool = types.SimpleNamespace(free_blocks=free_blocks,
                                 num_blocks=num_blocks,
                                 block_size=block_size)
    sched = types.SimpleNamespace(pool=pool, queue_depth=queue_depth,
                                  max_queue=max_queue)
    engine = types.SimpleNamespace(scheduler=sched)
    if peek is not None:
        engine.prefix_cache = types.SimpleNamespace(
            peek=lambda prompt, adapter=0: peek)
    h = types.SimpleNamespace(index=index, state=state, engine=engine)
    if role is not None:
        h.role = role
    return h


def test_router_stage_filters_by_role():
    r = Router()
    pools = [_handle(0, READY, role="prefill"),
             _handle(1, READY, role="decode"),
             _handle(2, READY)]  # unified handle, no role attr
    assert r.place(pools, 8, stage="prefill").index == 0
    assert r.place(pools, 8, stage="decode").index == 1
    # stage=None keeps the unified behavior: every READY is a candidate
    assert r.place(pools, 8) is not None
    # a role-bearing handle is NOT a "unified" candidate for the
    # other stage
    assert r.place(pools[:2], 8, stage="decode").index == 1


def test_router_prefill_pool_full_is_counted_no_replica():
    r = Router()
    pools = [_handle(0, "starting", role="prefill"),
             _handle(1, DEAD, role="prefill"),
             _handle(2, READY, role="decode")]  # decode can't prefill
    assert r.place(pools, 8, stage="prefill") is None
    reg = obs.get_registry()
    assert reg.counter("serve_router_placements_total").value(
        outcome="no_replica") == 1


def test_router_prefill_scores_queue_depth_not_kv_or_affinity():
    r = Router()
    # shallow queue wins even with a near-empty pool and a peer whose
    # prefix cache would dominate a decode-stage score
    starved = _handle(0, READY, role="prefill", free_blocks=1,
                      queue_depth=0)
    warm_busy = _handle(1, READY, role="prefill", free_blocks=16,
                        queue_depth=6, peek=8)
    prompt = np.arange(8, dtype=np.int32)
    assert r.place([starved, warm_busy], 8, prompt=prompt,
                   stage="prefill").index == 0


def test_router_decode_kv_exhausted_still_places():
    # negative headroom everywhere: the request queues FIFO on the
    # least-bad decode replica instead of being dropped
    r = Router()
    a = _handle(0, READY, role="decode", free_blocks=0)
    b = _handle(1, READY, role="decode", free_blocks=1)
    assert r.place([a, b], 8, stage="decode").index == 1


def test_router_decode_affinity_beats_headroom():
    r = Router()
    prompt = np.arange(8, dtype=np.int32)
    cold_idle = _handle(0, READY, role="decode", free_blocks=14, peek=0)
    warm_tight = _handle(1, READY, role="decode", free_blocks=6, peek=8)
    # full-prompt residency (weight 1.0) outbids a 50%-of-pool headroom
    # gap — the streamed blocks save real prefill work
    assert r.place([cold_idle, warm_tight], 8, prompt=prompt,
                   stage="decode").index == 1
    # without the prompt there is no affinity signal: headroom decides
    assert r.place([cold_idle, warm_tight], 8, stage="decode").index == 0


# ---------------------------------------------------------------------------
# Construction: the Fleet factory dispatch + pool validation
# ---------------------------------------------------------------------------

def test_fleet_kwargs_dispatch_and_pool_validation():
    with pytest.raises(ValueError, match="at least one replica"):
        Fleet(None, None, prefill=0, decode=2)
    with pytest.raises(ValueError, match="at least one replica"):
        Fleet(None, None, prefill=2, decode=0)
    with pytest.raises(TypeError, match="replicas"):
        DisaggFleet(None, None, prefill=1, decode=1, replicas=2)


# ---------------------------------------------------------------------------
# kill_transfer chaos grammar (no model: the hook is directly drivable)
# ---------------------------------------------------------------------------

def test_kill_transfer_fires_once_on_the_nth_transfer():
    chaos.maybe_init("kill_transfer@step=2", rank=0, incarnation=0,
                     seed=0)
    chaos.on_transfer(src=0, dst=1)  # ordinal 1: inert
    with pytest.raises(chaos.TransferKillError):
        chaos.on_transfer(src=0, dst=1)  # ordinal 2: fires
    chaos.on_transfer(src=0, dst=1)  # fired once; ordinal 3 is inert
    ring = [e for e in flight.get_recorder().snapshot()
            if e["kind"] == "chaos"]
    assert any(e["op"] == "kill_transfer" for e in ring), \
        "injection must be emitted (ring + counter) before it raises"


def test_kill_transfer_replica_narrows_to_source():
    chaos.maybe_init("kill_transfer@step=1:replica=3", rank=0,
                     incarnation=0, seed=0)
    # first transfer is from r0, not r3: the fault does not fire (and
    # step= is an exact ordinal, so it never will)
    chaos.on_transfer(src=0, dst=1)
    chaos.on_transfer(src=3, dst=1)


def test_on_transfer_is_inert_when_chaos_unset():
    chaos.on_transfer(src=0, dst=1)  # no engine: must be a no-op


# ---------------------------------------------------------------------------
# Fleet, synchronous drive (deterministic, no threads)
# ---------------------------------------------------------------------------

def test_disagg_sync_golden_streams_blocks_and_reuses_warmth(tiny_llama):
    """The acceptance criterion, sunny side: a disaggregated fleet's
    stitched greedy output is bit-identical to sequential ``generate``
    and to the unified fleet (budget 1 included — it finalizes at the
    handoff without a decode leg), the prompt's KV blocks travel
    through the collectives choke point (wire bytes + flight ring for
    free), and a repeat prompt lands on the already-warm decode
    replica without a second transfer."""
    model, params = tiny_llama
    prompts = _prompts([34, 6, 37, 9], seed=7)
    budgets = [2, 8, 1, 6]
    golden = _unified(model, params, prompts, budgets)
    with collectives.recording() as records:
        fleet = Fleet(model, params, prefill=1, decode=2, max_slots=2,
                      max_seq_len=64, block_size=16, max_queue=16)
        assert isinstance(fleet, DisaggFleet)
        tickets = [fleet.submit(p, n) for p, n in zip(prompts, budgets)]
        fleet.run_until_idle()
        for t, p, n in zip(tickets, prompts, budgets):
            assert t.ok, (t.status, t.reject_reason)
            np.testing.assert_array_equal(
                t.tokens, _golden(model, params, p, n))
        got = [list(t.tokens) for t in tickets]
        assert got == golden, f"disagg output diverged:\n{got}\n{golden}"
        # the long prompts (>= 2 full blocks) streamed their chains
        assert any(t["outcome"] == "ok" for t in fleet.transfers)
        n_before = len(fleet.transfers)
        # same prompt again: the decode pool already holds its blocks,
        # so affinity places it there and no new stream is needed
        t2 = fleet.submit(prompts[0], budgets[0])
        fleet.run_until_idle()
        assert t2.ok
        np.testing.assert_array_equal(
            t2.tokens, _golden(model, params, prompts[0], budgets[0]))
        assert len(fleet.transfers) == n_before
    xfers = [r for r in records if r.op == "kv_transfer"]
    assert xfers and all(r.bytes_wire > 0 for r in xfers), \
        "streamed blocks must land in goodput's wire-byte books"
    assert _fleet_ring("kv_transfer"), "transfer missing from the ring"
    assert _fleet_ring("handoff"), "handoff missing from the ring"
    reg = obs.get_registry()
    assert reg.counter("serve_kv_transfer_total").value(
        outcome="ok") == len([t for t in fleet.transfers
                              if t["outcome"] == "ok"])
    assert reg.counter("serve_kv_transfer_bytes").value() == \
        fleet.summary()["disagg"]["transfer_bytes"]
    g = reg.gauge("serve_fleet_replicas")
    assert g.value(role="prefill") == 1 and g.value(role="decode") == 2
    s = fleet.summary()["disagg"]
    assert s["prefill"] == 1 and s["decode"] == 2
    assert s["transfers_ok"] >= 1
    roles = {r["replica"]: r["role"] for r in
             fleet.summary()["per_replica"]}
    assert roles == {"r0": "prefill", "r1": "decode", "r2": "decode"}


def test_kill_transfer_failover_is_output_invariant(tiny_llama):
    """The acceptance criterion, rainy side: a source replica dying
    mid-transfer (chaos ``kill_transfer@``) burns the wire bytes, goes
    DEAD, and the decode leg re-prefills cold on a survivor — the
    stitched output does not change by a single token."""
    model, params = tiny_llama
    prompts = _prompts([34, 6, 37, 9], seed=7)
    budgets = [2, 8, 3, 6]
    golden = _unified(model, params, prompts, budgets)
    chaos.maybe_init("kill_transfer@step=1", rank=0, incarnation=0,
                     seed=0)
    fleet = Fleet(model, params, prefill=2, decode=2, max_slots=2,
                  max_seq_len=64, block_size=16, max_queue=16)
    tickets = [fleet.submit(p, n) for p, n in zip(prompts, budgets)]
    fleet.run_until_idle()
    for t, p, n in zip(tickets, prompts, budgets):
        assert t.ok, (t.status, t.reject_reason)
        np.testing.assert_array_equal(
            t.tokens, _golden(model, params, p, n))
    got = [list(t.tokens) for t in tickets]
    assert got == golden, \
        f"kill_transfer broke bit-identity:\n{got}\n{golden}"
    assert any(t["outcome"] == "failed" for t in fleet.transfers), \
        "the drill must actually kill a transfer"
    reg = obs.get_registry()
    assert reg.counter("serve_kv_transfer_total").value(
        outcome="failed") >= 1
    # failed transfers still burned the wire: bytes are on the books
    failed = [t for t in fleet.transfers if t["outcome"] == "failed"]
    assert all(t["bytes"] > 0 for t in failed)
    assert any(e["op"] == "state:dead" for e in _fleet_ring()), \
        "the transfer source must be declared dead"
