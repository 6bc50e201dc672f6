"""Continuous-batching serving engine (ISSUE 5 tentpole).

Covers the full stack bottom-up: KVPool reservation accounting,
Scheduler admission policy (backpressure / FIFO no-bypass / deadlines /
drain), the ServingEngine golden bit-identity vs sequential
``generate`` (the acceptance criterion: sharing a batch with strangers
must not perturb a row's floats), the anti-starvation bound under
sustained overload, chaos integration (``serve_reject@p=`` load-shed,
``slow@`` stretching decode rounds), and the SIGTERM drain of
``scripts/serve.py`` (subprocess, GRACEFUL_EXIT_CODE).
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.inference.generate import generate, init_cache
from pytorch_distributed_nn_tpu.nn.lora import init_lora_bank
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import (
    DecodeSpec,
    InferenceServer,
    KVPool,
    Scheduler,
    ServingEngine,
    open_loop_client,
    ragged_prompt_sampler,
)
from pytorch_distributed_nn_tpu.serve import engine as engine_mod

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


# tiny_llama comes from conftest.py (session-scoped): one model shared
# with test_prefix_cache.py so the serve jits compile once per session.


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=(n,)).astype(np.int32)
            for n in lengths]


def _serve_ring_ops():
    return [e["op"] for e in flight.get_recorder().snapshot()
            if e["kind"] == "serve"]


# ---------------------------------------------------------------------------
# KVPool
# ---------------------------------------------------------------------------

def test_pool_reserve_extend_free_accounting():
    pool = KVPool(num_blocks=8, block_size=4)
    assert pool.blocks_for(1) == 1 and pool.blocks_for(4) == 1
    assert pool.blocks_for(5) == 2 and pool.blocks_for(0) == 0

    assert pool.reserve("a", 9)  # 3 blocks
    assert pool.free_blocks == 5
    assert len(pool.block_table("a")) == 3
    assert pool.reserve("b", 17)  # 5 blocks
    assert pool.free_blocks == 0
    assert pool.utilization() == 1.0
    # pool exhausted: reserve fails WITHOUT state change
    assert not pool.reserve("c", 1)
    assert pool.live_sequences == 2

    pool.extend("a", 8)  # inside reservation: fine
    with pytest.raises(ValueError):
        pool.extend("a", 13)  # past the 3-block reservation
    with pytest.raises(KeyError):
        pool.extend("nope", 1)
    with pytest.raises(ValueError):
        pool.reserve("a", 1)  # double reservation is a bug

    assert pool.free("a") == 3
    assert pool.free_blocks == 3
    assert pool.free("a") == 0  # unknown id: benign no-op
    assert pool.free("b") == 5
    assert pool.utilization() == 0.0
    assert pool.block_table("b") == ()


def test_pool_publishes_utilization_gauges():
    pool = KVPool(num_blocks=4, block_size=2)
    reg = obs.get_registry()
    assert reg.gauge("serve_kv_blocks_total").value() == 4
    pool.reserve("s", 5)  # 3 blocks
    assert reg.gauge("serve_kv_blocks_reserved").value() == 3
    pool.extend("s", 3)
    assert reg.gauge("serve_kv_blocks_used").value() == 2
    pool.free("s")
    assert reg.gauge("serve_kv_blocks_reserved").value() == 0


# ---------------------------------------------------------------------------
# Scheduler policy (no model needed)
# ---------------------------------------------------------------------------

def _sched(num_blocks=16, block_size=4, **kw):
    return Scheduler(KVPool(num_blocks, block_size), **kw)


def test_backpressure_bounded_queue():
    s = _sched(max_queue=2)
    a = s.submit([1, 2], 4)
    b = s.submit([3], 4)
    c = s.submit([4], 4)
    assert a.state == "queued" and b.state == "queued"
    assert c.state == "rejected" and c.reject_reason == "backpressure"
    assert c.done.is_set()  # rejected clients unblock immediately
    reg = obs.get_registry()
    assert reg.counter("serve_rejects_total").value(
        reason="backpressure") == 1


def test_too_large_rejected_at_submit():
    s = _sched(max_seq_len=16)
    r = s.submit(np.arange(1, 13), 8)  # 12 + 8 > 16
    assert r.state == "rejected" and r.reject_reason == "too_large"
    ok = s.submit(np.arange(1, 9), 8)  # 8 + 8 == 16: fits
    assert ok.state == "queued"


def test_fifo_no_bypass_when_head_does_not_fit():
    # pool of 4 blocks * 4 tokens = 16; head wants 5 blocks (20 tokens)
    s = _sched(num_blocks=4, block_size=4)
    big = s.submit(np.ones(12), 8)  # 20 tokens: can never... fit 5 > 4
    small = s.submit([1], 3)        # 1 block: would fit
    assert s.next_admissions(free_slots=4) == []  # no leapfrogging
    assert big.state == "queued" and small.state == "queued"
    assert s.queue_depth == 2


def test_admission_caps_at_max_prefills_per_round():
    s = _sched(max_prefills_per_round=2)
    reqs = [s.submit([1, 2], 2) for _ in range(5)]
    first = s.next_admissions(free_slots=5)
    assert [r.request_id for r in first] == \
        [r.request_id for r in reqs[:2]]
    assert all(r.state == "running" for r in first)


def test_expired_deadline_rejected_not_admitted():
    s = _sched()
    late = s.submit([1], 2, deadline_s=time.monotonic() - 0.1)
    live = s.submit([2], 2)
    got = s.next_admissions(free_slots=2)
    assert late.state == "rejected" and late.reject_reason == "deadline"
    assert got == [live]


def test_drain_rejects_queued_and_future_submits():
    s = _sched()
    q = s.submit([1], 2)
    assert s.drain() == 1
    assert q.state == "rejected" and q.reject_reason == "draining"
    post = s.submit([2], 2)
    assert post.state == "rejected" and post.reject_reason == "draining"
    assert s.queue_depth == 0


def test_every_transition_counted_and_rejects_flight_visible():
    s = _sched(max_queue=1)
    s.submit([1], 2)                 # queued
    s.submit([2], 2)                 # backpressure
    s.next_admissions(free_slots=1)  # running
    reg = obs.get_registry()
    c = reg.counter("serve_requests_total")
    assert c.value(state="queued") == 1
    assert c.value(state="rejected") == 1
    assert c.value(state="running") == 1
    ops = [e["op"] for e in flight.get_recorder().snapshot()
           if e["kind"] == "serve"]
    assert "reject:backpressure" in ops


# ---------------------------------------------------------------------------
# Engine: the golden bit-identity acceptance criterion
# ---------------------------------------------------------------------------

def test_engine_greedy_bit_identical_to_sequential(tiny_llama):
    """8 ragged requests through 3 slots — mid-batch retirements and
    joins throughout — must produce for every request exactly the
    tokens of a solo sequential generate() of that prompt."""
    model, params = tiny_llama
    prompts = _prompts([5, 11, 3, 17, 8, 2, 9, 6], seed=1)
    n_new = 7
    eng = ServingEngine(model, params, max_slots=3, max_seq_len=64,
                        block_size=8, max_queue=16,
                        max_prefills_per_round=2)
    srv = InferenceServer(eng).start()
    try:
        reqs = [srv.submit(p, n_new) for p in prompts]
        for r in reqs:
            assert r.done.wait(300), r.request_id
    finally:
        srv.stop()
    for p, r in zip(prompts, reqs):
        assert r.state == "done", (r.state, r.reject_reason)
        ref = np.asarray(generate(model, params, p[None], n_new))
        np.testing.assert_array_equal(r.tokens, ref[0, len(p):])
    # engine-level accounting agrees with what clients got back
    reg = obs.get_registry()
    assert reg.counter("serve_tokens_total").value() == 8 * n_new
    summ = eng.summary()
    assert summ["requests_done"] == 8
    assert summ["tokens_out"] == 8 * n_new
    assert 0.0 < summ["occupancy"] <= 1.0
    assert eng.scheduler.pool.live_sequences == 0  # all blocks freed
    ops = _serve_ring_ops()
    assert "admit" in ops and "retire" in ops and "decode_round" in ops


def test_engine_budget_one_matches_prefill_argmax(tiny_llama):
    """A max_new_tokens=1 request retires straight from prefill; the
    single token must equal the sequential path's first token."""
    model, params = tiny_llama
    (p,) = _prompts([9], seed=3)
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=32)
    r = eng.submit(p, 1)
    eng.run_until_idle()
    ref = np.asarray(generate(model, params, p[None], 1))
    assert r.state == "done"
    np.testing.assert_array_equal(r.tokens, ref[0, len(p):])


# ---------------------------------------------------------------------------
# The zeroed cache a prefill writes into (ISSUE 28): one compiled
# program, the same tree as init_cache's, fresh buffers on every call
# ---------------------------------------------------------------------------

class _MaskedLoraStub:
    """Stands where a model stands in the step, for a model that takes
    both a bank and the token mask. Row i's next token is its adapter's
    id plus the bank's shift; the mask it was given comes back as the
    cache."""

    takes_token_mask = True

    def apply(self, variables, tokens, *, lora_bank, adapter_ids,
              token_mask, **kw):
        logits = jax.nn.one_hot(adapter_ids + lora_bank["shift"], 8)
        return logits[:, None, :], {"cache": {"mask": token_mask}}


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_token_mask_reaches_the_step_of_an_engine_with_a_bank(sampled):
    """A model that takes ``token_mask`` gets the active rows' mask in
    every combination the step has: with a bank, and with a bank and
    sampled rows (temperature 0 here, so the rows keep their argmax).
    The mask that comes back is the next round's: row 0 has emitted its
    budget's last token, row 1 was not active, row 2 goes on."""
    active = np.asarray([True, False, True])
    sampling = None
    if sampled:
        sampling = {k: np.zeros((3,), dt)
                    for k, dt in engine_mod._SAMPLING_ROW.items()}
    nxt, lengths, alive, remaining, cache, drawn = engine_mod._serve_step(
        _MaskedLoraStub(), {}, {"mask": np.zeros((3, 1), bool)},
        np.asarray([7, 7, 7], np.int32), np.asarray([4, 5, 6], np.int32),
        active, np.asarray([1, 4, 3], np.int32), np.int32(-1),
        dict(lora_bank={"shift": np.int32(2)},
             adapter_ids=np.asarray([0, 1, 3], np.int32)),
        sampling)
    np.testing.assert_array_equal(cache["mask"], active[:, None])
    np.testing.assert_array_equal(nxt, [2, 7, 5])
    np.testing.assert_array_equal(lengths, [5, 5, 7])
    np.testing.assert_array_equal(alive, [False, False, True])
    np.testing.assert_array_equal(remaining, [0, 4, 2])
    assert (drawn is None) == (not sampled)
    if sampled:
        np.testing.assert_array_equal(drawn["step"], [1, 0, 1])


def test_an_admission_writes_its_rows_and_what_a_program_reads(
        tiny_llama, monkeypatch):
    """An admission hands the device one (5, slots) array that marks
    the rows it filled, and nothing of the others; the adapter ids go
    only in an engine with a bank; the sampling rows only while a
    sampled row is live. A retire writes nothing."""
    model, params = tiny_llama
    calls = []
    write = engine_mod._write_rows
    monkeypatch.setattr(
        engine_mod, "_write_rows",
        lambda *a: (calls.append(a), write(*a))[1])

    def admitted(eng, **kw):
        """What the one write of a request's admission was given."""
        del calls[:]
        eng.submit(np.arange(1, 9, dtype=np.int32), 4, **kw)
        eng.step()
        (call,) = calls
        eng.run_until_idle()
        assert len(calls) == 1      # the retire wrote nothing
        return call[4], call[5:]

    kw = dict(max_slots=2, max_seq_len=64, block_size=8)
    eng = ServingEngine(model, params, **kw)
    assert eng._lora is None
    rows, rest = admitted(eng)
    np.testing.assert_array_equal(rows[0], [1, 0])      # slot 0 alone
    assert rows[2, 0] == 8 and rows[3, 0] == 3  # depth, tokens left
    assert rest == (None, None, None)
    bank = init_lora_bank(model, num_adapters=2, rank=2)
    rows, (ids, *sampling) = admitted(
        ServingEngine(model, params, lora_bank=bank, **kw), adapter=1)
    assert ids is not None and rows[4, 0] == 1 and sampling == [None, None]
    _, (_, given, drawn) = admitted(
        eng, decode=DecodeSpec(temperature=0.8, seed=1))
    assert set(given) == set(drawn) == set(engine_mod._SAMPLING_ROW)
    assert eng._n_sampled == 0
    assert admitted(eng)[1] == (None, None, None)


@pytest.mark.parametrize("with_bank", [False, True], ids=["plain", "bank"])
def test_warmup_compiles_what_the_engine_then_runs(tiny_llama, with_bank):
    """After ``warmup`` greedy requests of a warmed prompt bucket
    compile nothing, with a bank or without, and the warm-up leaves
    the engine as it found it. An admission's write of its rows' slot
    state is one program of one shape, whether a pass fills one row or
    two."""
    model, params = tiny_llama
    bank = init_lora_bank(model, num_adapters=2, rank=2) \
        if with_bank else None
    # a batch size no other test of the session uses: the step is new
    # here (a prefill's row knows nothing of the batch)
    eng = ServingEngine(model, params, max_slots=5, max_seq_len=80,
                        block_size=8, lora_bank=bank)
    programs = (engine_mod._zero_cache, engine_mod._serve_prefill,
                engine_mod._insert_row, engine_mod._serve_step,
                engine_mod._write_rows)
    cold = [f._cache_size() for f in programs]
    eng.warmup((8,))
    warm = [f._cache_size() for f in programs]
    assert warm[3] > cold[3] and warm[4] > cold[4]
    assert eng.active_slots == 0 and not eng.has_work
    for n in (1, 2):   # prompts that share no prefix: no restore
        reqs = [eng.submit(np.arange(8, dtype=np.int32) + 10 * (n + k), 3,
                           adapter=int(with_bank)) for k in range(n)]
        eng.run_until_idle()
        assert all(r.state == "done" for r in reqs)
        assert [f._cache_size() for f in programs] == warm


@pytest.mark.parametrize("batch", [1, 3], ids=["row", "max_slots"])
def test_zero_cache_is_init_caches_tree_leaf_for_leaf(tiny_llama, batch):
    model, _ = tiny_llama
    got = engine_mod._fresh_cache(model, batch, 32)
    want = init_cache(model, batch, 32)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    leaves = jax.tree.leaves(got)
    assert len(leaves) == 3 * 2     # key, value, index of two layers
    for g, w in zip(leaves, jax.tree.leaves(want)):
        assert (g.shape, g.dtype, g.weak_type) \
            == (w.shape, w.dtype, w.weak_type)
        assert g.sharding == w.sharding and g.committed == w.committed
        assert not np.asarray(g).any()


def test_zero_cache_never_hands_out_a_buffer_twice(tiny_llama):
    """The row cache is donated to the prefill program: two calls, and
    two leaves of one call, must not share a buffer."""
    model, _ = tiny_llama
    first = jax.tree.leaves(engine_mod._fresh_cache(model, 1, 16))
    again = jax.tree.leaves(engine_mod._fresh_cache(model, 1, 16))
    ptrs = [x.unsafe_buffer_pointer() for x in first + again]
    assert len(set(ptrs)) == len(ptrs)


def test_two_admissions_of_one_bucket_back_to_back(tiny_llama):
    """Both prefills of a pass take the 16-token bucket's zero cache
    and both donate it; the second must not get the first one's."""
    model, params = tiny_llama
    prompts = _prompts([9, 12], seed=8)
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=32,
                        max_prefills_per_round=2)
    reqs = [eng.submit(p, 4) for p in prompts]
    eng.step()
    assert eng.active_slots == 2    # one pass admitted both
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.state == "done", (r.state, r.reject_reason)
        ref = np.asarray(generate(model, params, p[None], 4))
        np.testing.assert_array_equal(r.tokens, ref[0, len(p):])


def _tokens_of(kind, model, params, p):
    """The tokens of one request of ``kind`` for prompt ``p`` through a
    new engine, and how many prompt tokens the prefix cache gave."""
    eng = ServingEngine(model, params, max_slots=3, max_seq_len=64,
                        block_size=16)
    if kind == "sampled":
        spec = DecodeSpec(temperature=0.9, top_k=20, best_of=2, n=2,
                          seed=5)
        r = eng.submit(p, 6, decode=spec)
        eng.run_until_idle()
        assert r.state == "done" and len(r.n_best) == 2
        return [list(map(int, b["tokens"])) for b in r.n_best], 0
    if kind == "hit":       # the same prompt again: restore, then prefill
        eng.submit(p, 2)
        eng.run_until_idle()
    r = eng.submit(p, 6)
    eng.run_until_idle()
    assert r.state == "done"
    return list(map(int, r.tokens)), eng.completed[-1]["cached_tokens"]


@pytest.mark.parametrize("kind", ["miss", "hit", "sampled"])
def test_tokens_are_the_eager_zero_caches(tiny_llama, monkeypatch, kind):
    """Greedy tokens of a miss, of a prefix hit (``_restore_blocks``
    into the zero row) and both branches of a sampled request: what the
    engine gave when ``_fresh_cache`` minted its zeros eagerly."""
    model, params = tiny_llama
    (p,) = _prompts([21], seed=12)
    got, cached = _tokens_of(kind, model, params, p)
    assert cached == (16 if kind == "hit" else 0)
    # init_cache is what _fresh_cache was before ISSUE 28: every leaf
    # an eager jnp.zeros on the host
    monkeypatch.setattr(engine_mod, "_fresh_cache", init_cache)
    assert _tokens_of(kind, model, params, p) == (got, cached)
    if kind != "sampled":
        ref = np.asarray(generate(model, params, p[None], 6))
        assert got == list(map(int, ref[0, len(p):]))


def test_engine_ttft_and_latency_histograms_populated(tiny_llama):
    model, params = tiny_llama
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=32)
    for p in _prompts([4, 6], seed=5):
        eng.submit(p, 3)
    eng.run_until_idle()
    reg = obs.get_registry()
    assert reg.histogram("serve_ttft_seconds").snapshot()["count"] == 2
    # 2 interleaved streams x 3 tokens: first tokens come from prefill,
    # the remaining 2 per stream from shared decode rounds
    assert reg.histogram(
        "serve_token_latency_seconds").snapshot()["count"] >= 2
    assert len(eng.completed) == 2
    for rec in eng.completed:
        assert rec["ttft_s"] > 0 and rec["per_token_s"] > 0


# ---------------------------------------------------------------------------
# Anti-starvation under sustained overload
# ---------------------------------------------------------------------------

def test_no_starvation_bounded_rounds_under_overload(tiny_llama):
    """Strict FIFO + reservation-at-admission: with the queue full the
    whole run, every request still completes, admission order equals
    submission order, and no request waits more than (queue ahead /
    slots + 1) waves of the longest budget."""
    model, params = tiny_llama
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=32,
                        block_size=8, max_queue=32,
                        max_prefills_per_round=2)
    budgets = [6, 2, 4, 6, 2, 4, 6, 2, 4, 6, 2, 4]
    prompts = _prompts([7, 3, 5, 9, 4, 6, 8, 3, 5, 7, 4, 6], seed=7)
    reqs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    admit_rounds = [r.round_admitted for r in reqs]
    assert admit_rounds == sorted(admit_rounds), \
        "FIFO violated: a later submit was admitted earlier"
    waits = [r.round_admitted - r.round_submitted for r in reqs]
    # 12 requests / 2 slots = 6 waves of at most max(budgets) rounds
    bound = (len(reqs) // 2 + 1) * max(budgets)
    assert max(waits) <= bound, (waits, bound)


# ---------------------------------------------------------------------------
# Chaos integration
# ---------------------------------------------------------------------------

def test_chaos_serve_reject_sheds_load_without_deadlock(tiny_llama):
    """serve_reject@p= sheds admissions: every request still reaches a
    terminal state, rejects are counted AND flight-visible, accepted
    ones still finish (no deadlock under load shedding)."""
    model, params = tiny_llama
    chaos.maybe_init("serve_reject@p=0.5", rank=0, seed=11)
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=32,
                        max_queue=4)
    reqs = [eng.submit(p, 2) for p in _prompts([4] * 20, seed=9)]
    eng.run_until_idle()
    states = {r.state for r in reqs}
    assert states <= {"done", "rejected"}
    shed = [r for r in reqs if r.reject_reason == "chaos"]
    assert 0 < len(shed) < 20, "p=0.5 over 20 must shed some, not all"
    assert all(r.done.is_set() for r in reqs)
    reg = obs.get_registry()
    assert reg.counter("serve_rejects_total").value(
        reason="chaos") == len(shed)
    assert reg.counter("chaos_injected_total").value(
        kind="serve_reject") == len(shed)
    ring = flight.get_recorder().snapshot()
    assert sum(1 for e in ring if e["kind"] == "chaos"
               and "serve_reject" in e["op"]) == len(shed)


def test_chaos_serve_reject_is_deterministic():
    def run():
        chaos.reset()
        chaos.maybe_init("serve_reject@p=0.4", rank=0, seed=5)
        s = _sched()
        return [s.submit([1, 2], 2).state for _ in range(30)]

    assert run() == run()


def test_chaos_slow_stretches_decode_rounds(tiny_llama):
    """slow@ keys on the serving round exactly like a training step: an
    injected 30ms stall must show up in the engine's per-round wall
    times (and therefore the latency histograms)."""
    model, params = tiny_llama
    eng0 = ServingEngine(model, params, max_slots=1, max_seq_len=32)
    (p,) = _prompts([5], seed=13)
    eng0.submit(p, 4)
    eng0.run_until_idle()  # warm jits so the timed engine is compile-free

    chaos.maybe_init("slow@rank=0:ms=30", rank=0, seed=0)
    eng = ServingEngine(model, params, max_slots=1, max_seq_len=32)
    r = eng.submit(p, 4)
    eng.run_until_idle()
    assert r.state == "done"
    assert len(eng.round_seconds) == 3  # 3 decode rounds after prefill
    assert min(eng.round_seconds) >= 0.025, eng.round_seconds


# ---------------------------------------------------------------------------
# Server thread + drain
# ---------------------------------------------------------------------------

def test_open_loop_overload_degrades_gracefully(tiny_llama):
    """Open-loop arrivals far above service rate against a tiny queue:
    bounded memory (queue never exceeds max_queue), overflow rejected
    as backpressure, admitted requests all finish bit-exactly-typed
    terminal — and nothing deadlocks."""
    model, params = tiny_llama
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=64,
                        block_size=8, max_queue=3)
    srv = InferenceServer(eng).start()
    try:
        sampler = ragged_prompt_sampler(VOCAB, min_len=4, max_len=12,
                                        seed=2)
        reqs = open_loop_client(srv, num_requests=30, rate_hz=2000.0,
                                max_new_tokens=4, prompt_sampler=sampler)
    finally:
        srv.stop()
    assert len(reqs) == 30
    assert all(r.done.is_set() for r in reqs)
    done = [r for r in reqs if r.ok]
    shed = [r for r in reqs if r.reject_reason == "backpressure"]
    assert len(done) + len(shed) == 30
    assert done, "some requests must survive"
    assert shed, "2000 req/s into a 3-deep queue must shed"
    reg = obs.get_registry()
    assert reg.counter("serve_rejects_total").value(
        reason="backpressure") == len(shed)


def test_server_stop_drains_in_flight(tiny_llama):
    model, params = tiny_llama
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=64,
                        max_queue=16)
    srv = InferenceServer(eng).start()
    reqs = [srv.submit(p, 5) for p in _prompts([6] * 6, seed=4)]
    srv.stop()  # immediate stop: drain rejects queued, finishes running
    assert all(r.done.is_set() for r in reqs)
    for r in reqs:
        if r.ok:
            assert len(r.tokens) == 5  # finished its full budget
        else:
            assert r.reject_reason == "draining"
    ops = _serve_ring_ops()
    assert "server_start" in ops and "server_stop" in ops
    assert "drained" in ops


def _spawn_serve_cli(tmp_path, requests=200, rate=20.0):
    repo = Path(__file__).parent.parent
    out = tmp_path / "serve.jsonl"
    tiny = ('{"num_layers":1,"d_model":32,"num_heads":2,"num_kv_heads":1,'
            '"mlp_dim":64,"vocab_size":64}')
    # stderr goes to a file: nobody reads a pipe while the test waits,
    # and a child that logs more than a pipe holds (XLA warns ~2 KB a
    # program it loads from the compile cache) would block in write()
    err = (tmp_path / "serve.err").open("w")
    proc = subprocess.Popen(
        [sys.executable, str(repo / "scripts" / "serve.py"),
         "--preset", "llama3_8b_zero", "--slots", "2",
         "--max-seq-len", "32", "--requests", str(requests),
         "--rate", str(rate), "--max-new", "4", "--min-prompt", "4",
         "--max-prompt", "8", "--metrics-out", str(out),
         "--model.extra", tiny, "--model.compute_dtype", "float32",
         "--model.remat", "false"],
        cwd=repo, stdout=subprocess.PIPE, stderr=err,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "TPUNN_CHAOS": ""},
    )
    err.close()
    return proc, out


def test_sigterm_drains_and_exits_graceful_code(tmp_path):
    """The acceptance criterion: SIGTERM mid-load -> queued rejected,
    in-flight finished, one JSON summary, GRACEFUL_EXIT_CODE (83)."""
    from pytorch_distributed_nn_tpu.runtime.failure import (
        GRACEFUL_EXIT_CODE,
    )

    proc, out = _spawn_serve_cli(tmp_path)
    try:
        # wait for proof of TIMED in-flight serving before pulling the
        # plug (>3 records: the CLI's warmup request also emits one —
        # a SIGTERM landing mid-warmup would drain into 0 completions)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if (out.exists()
                    and out.read_bytes().count(b"serve_request") > 3):
                break
            if proc.poll() is not None:
                pytest.fail(f"serve.py exited early: "
                            f"{(tmp_path / 'serve.err').read_text()[-2000:]}")
            time.sleep(0.1)
        else:
            pytest.fail("no serve_request event before timeout")
        proc.send_signal(signal.SIGTERM)
        stdout, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == GRACEFUL_EXIT_CODE, \
        (proc.returncode, (tmp_path / "serve.err").read_text()[-2000:])
    summary = json.loads(stdout.strip().splitlines()[-1])
    assert summary["preempted"] is True
    assert summary["completed"] >= 1
    # drained, not dropped: every submitted request reached a terminal
    # state (completed or explicitly rejected), none abandoned
    assert summary["completed"] + summary["rejected"] \
        <= summary["requests"]


# ---------------------------------------------------------------------------
# Metrics plumbing + obs_report
# ---------------------------------------------------------------------------

def test_serve_request_jsonl_and_obs_report_section(tiny_llama, tmp_path):
    from pytorch_distributed_nn_tpu.utils.metrics import MetricsLogger

    model, params = tiny_llama
    out = tmp_path / "m.jsonl"
    with MetricsLogger(str(out)) as m:
        eng = ServingEngine(model, params, max_slots=2, max_seq_len=32,
                            metrics=m)
        for p in _prompts([4, 7, 5], seed=6):
            eng.submit(p, 3)
        eng.run_until_idle()
    events = [json.loads(ln) for ln in out.read_text().splitlines()]
    reqs = [e for e in events if e["event"] == "serve_request"]
    assert len(reqs) == 3
    for e in reqs:
        assert e["new_tokens"] == 3
        assert e["ttft_s"] > 0 and e["per_token_s"] > 0
        assert 0.0 <= e["kv_util"] <= 1.0

    repo = Path(__file__).parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "scripts" / "obs_report.py"),
         str(out)],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    assert "== serving ==" in proc.stdout
    assert "ttft_s" in proc.stdout


def test_obs_report_no_serve_events_no_traceback(tmp_path):
    out = tmp_path / "train_only.jsonl"
    out.write_text('{"event": "train_step", "step": 1, "loss": 1.0}\n')
    repo = Path(__file__).parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "scripts" / "obs_report.py"),
         str(out)],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert "Traceback" not in proc.stderr
    assert "== serving ==" not in proc.stdout
