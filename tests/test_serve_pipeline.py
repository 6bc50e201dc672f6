"""The serve loop keeps one decode round in flight (ISSUE 32).

``ServingEngine._decode_round`` dispatches round n+1 and then fetches
round n's tokens, so the host's work of a round runs while the chip
computes the next. That is a reordering of host work and must be
*exact*: a row stops on the device, in the step that produces its last
token (budget or eos); an admission writes the slot state of its own
rows and of no other; a round dispatched before a row was admitted has
nothing of it. Here, on the CPU with the tiny Llama and the small
LongCat: every request's tokens are those of the same engine driven
one round at a time (dispatch, fetch, host work: the parent's order)
and, for the Llama, of sequential ``generate``; the loop's edges leave
nothing in flight; the call order is the overlapped one and the
counter says so.
"""

import time

import jax
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.inference.generate import generate
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import (
    DecodeSpec,
    InferenceServer,
    ServingEngine,
)
from pytorch_distributed_nn_tpu.serve import engine as engine_mod

SLOTS, MAX_SEQ = 3, 48
ENGINE = dict(max_slots=SLOTS, max_seq_len=MAX_SEQ, block_size=8,
              max_queue=16, max_prefills_per_round=2)
# prompt lengths and budgets: more requests than slots, so every slot
# is refilled in the step after the one that freed it; ragged budgets,
# so rows retire while others go on
JOBS = ((5, 9), (11, 4), (3, 12), (17, 2), (8, 7), (2, 10), (9, 1),
        (6, 6))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    chaos.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    yield
    chaos.reset()


@pytest.fixture(scope="module")
def small_longcat():
    """Rank 1 of 4 of the small LongCat, weights as its own tests draw
    them."""
    import test_longcat_flash as lc

    cfg, model = lc._cfg(4, 1), lc._model(4, 1)
    return model, lc.weights.tree(lc.SEED, lc.ref.param_spec(cfg))


@pytest.fixture(params=["llama", "longcat"])
def served(request, tiny_llama):
    """(family, model, params, vocabulary)."""
    if request.param == "llama":
        return ("llama",) + tuple(tiny_llama) + (97,)
    return ("longcat",) + request.getfixturevalue("small_longcat") + (256,)


def _prompts(vocab, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=(n,)).astype(np.int32)
            for n in lengths]


def _one_round_at_a_time(eng):
    """The parent's loop order from the same engine: a call of
    ``_decode_round`` never dispatches beyond the round it fetches."""
    eng._rows_outlast_flight = lambda: False
    return eng


def _serve(model, params, prompts, budgets, *, sequential=False, **kw):
    eng = ServingEngine(model, params, **{**ENGINE, **kw})
    if sequential:
        _one_round_at_a_time(eng)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    assert not eng._flight and not eng.has_work
    # the device stopped every row itself: the host wrote none off
    assert not np.asarray(eng._d_slots[2]).any()
    return [list(map(int, r.tokens)) for r in reqs], eng


def _cut(stream, eos):
    """A stream as a loop that stops at ``eos`` serves it."""
    return stream[:stream.index(eos) + 1] if eos in stream else stream


def _eos_inside(streams):
    """A token that ends one of ``streams`` early, and not at once:
    (the token, the stream's index, where it falls)."""
    for i, s in enumerate(streams):
        for j in range(1, len(s) - 1):
            if s[j] not in s[:j]:
                return s[j], i, j
    raise AssertionError(f"no stream of {streams} has a token to stop at")


def test_step_stops_a_row_in_the_round_of_its_last_token(tiny_llama):
    """The rule itself, on the program: an active row goes on while it
    has more than this round's token left and the token is not the stop
    token; an inactive row stays as it is, whatever its numbers say."""
    model, params = tiny_llama
    cache = engine_mod._fresh_cache(model, 4, 16)
    active = np.asarray([True, True, False, True])
    nxt, depth, alive, remaining, _, _ = engine_mod._serve_step(
        model, params, cache, np.asarray([3, 4, 5, 6], np.int32),
        np.asarray([2, 2, 2, 2], np.int32), active,
        np.asarray([1, 2, 9, 9], np.int32), np.int32(-1))
    np.testing.assert_array_equal(alive, [False, True, False, True])
    np.testing.assert_array_equal(remaining, [0, 1, 9, 8])
    np.testing.assert_array_equal(depth, [3, 3, 2, 3])
    assert int(nxt[2]) == 5
    # the same round again with row 3's token as the stop token
    cache = engine_mod._fresh_cache(model, 4, 16)
    _, _, alive, _, _, _ = engine_mod._serve_step(
        model, params, cache, np.asarray([3, 4, 5, 6], np.int32),
        np.asarray([2, 2, 2, 2], np.int32), active,
        np.asarray([1, 2, 9, 9], np.int32), np.int32(int(nxt[3])))
    np.testing.assert_array_equal(
        alive, [False, int(nxt[1]) != int(nxt[3]), False, False])


def test_write_rows_sets_the_written_rows_and_no_other():
    last, depth, remaining = (np.asarray(v, np.int32) for v in
                              ([7, 8, 9], [4, 5, 6], [3, 0, 2]))
    active = np.asarray([True, False, True])
    rows = np.asarray([[0, 1, 1], [0, 11, 12], [0, 21, 22], [0, 5, 0],
                       [0, 1, 0]], np.int32)
    out, ids, sampling = engine_mod._write_rows(
        last, depth, active, remaining, rows,
        np.asarray([2, 2, 2], np.int32))
    for got, want in zip(out, ([7, 11, 12], [4, 21, 22],
                               [True, True, False], [3, 5, 0])):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ids, [2, 1, 0])
    assert sampling is None
    mirror = {k: np.full((3,), 5, dt)
              for k, dt in engine_mod._SAMPLING_ROW.items()}
    device = jax.tree.map(np.zeros_like, mirror)
    _, ids, drawn = engine_mod._write_rows(
        last, depth, active, remaining, rows, None, device, mirror)
    assert ids is None
    for k, v in drawn.items():
        # a spec and an RNG lane are the host's in every slot; a step
        # and a running logprob only in the written ones
        np.testing.assert_array_equal(
            v, [0, 5, 5] if k in ("step", "logprob") else [5, 5, 5])


def test_streams_with_ragged_budgets_through_reused_slots(served):
    """(c) every slot is refilled in the step after the one that freed
    it, while the round dispatched before the admission is still
    unfetched: its token for that slot is the last occupant's and must
    reach nobody."""
    family, model, params, vocab = served
    prompts = _prompts(vocab, [n for n, _ in JOBS], seed=1)
    budgets = [k for _, k in JOBS]
    want, seq = _serve(model, params, prompts, budgets, sequential=True)
    got, eng = _serve(model, params, prompts, budgets)
    assert got == want
    assert [len(s) for s in got] == budgets
    assert seq.summary()["rounds_overlapped"] == 0
    assert eng.summary()["rounds_overlapped"] > 0
    assert eng.scheduler.pool.live_sequences == 0
    if family == "llama":
        for p, k, s in zip(prompts, budgets, got):
            ref = np.asarray(generate(model, params, p[None], k))
            assert s == list(map(int, ref[0, len(p):]))


def test_an_eos_falls_while_the_next_round_is_in_flight(served):
    """(a) a row meets the stop token in round n while round n+1 is
    already dispatched: the device stopped it in round n, so its stream
    ends with that token, and the others' streams are those of a loop
    that never saw it end."""
    _, model, params, vocab = served
    prompts = _prompts(vocab, [n for n, _ in JOBS], seed=2)
    budgets = [12] * len(JOBS)
    full, _ = _serve(model, params, prompts, budgets, sequential=True)
    eos, who, where = _eos_inside(full)
    want = [_cut(s, eos) for s in full]
    assert len(want[who]) == where + 1 < budgets[who]
    got, eng = _serve(model, params, prompts, budgets, eos_token=eos)
    assert got == want
    seq, _ = _serve(model, params, prompts, budgets, eos_token=eos,
                    sequential=True)
    assert seq == want
    assert eng.scheduler.pool.live_sequences == 0


def test_a_row_ends_at_the_caches_last_position(served):
    """(b) a row's last token, the stop token, falls with the row at
    ``max_seq_len - 1``: a round more would write past the row's cache.
    A witness decodes beside it from before to long after; its tokens
    and its row of the cache are those of a loop in which it was
    alone."""
    family, model, params, vocab = served
    (witness,) = _prompts(vocab, [4], seed=3)
    (probe,) = _prompts(vocab, [MAX_SEQ - 6], seed=4)
    alone, solo = _serve(model, params, [witness], [30], sequential=True)
    (tail,), _ = _serve(model, params, [probe], [6], sequential=True)
    # the stop token is the probe's last if nothing before it is; the
    # budget ends the row there all the same
    eos = tail[-1] if tail[-1] not in tail[:-1] + alone[0] else None
    (late,) = _prompts(vocab, [7], seed=5)
    got, eng = _serve(model, params, [witness, probe, late], [30, 6, 5],
                      eos_token=eos)
    assert got[0] == alone[0] and got[1] == tail
    # each row stopped in the round of its last token: its depth on the
    # device is where the host's mirror ended, the probe's at the
    # cache's last position, and no budget is overdrawn
    _, depths, _, remaining = (np.asarray(x) for x in eng._d_slots)
    np.testing.assert_array_equal(
        depths, [len(witness) + 30 - 1, MAX_SEQ - 1, len(late) + 5 - 1])
    np.testing.assert_array_equal(remaining, [0, 0, 0])
    depth = len(witness) + 30 - 1
    tol = 0.0 if family == "llama" else 1e-5
    for a, b in zip(jax.tree.leaves(eng._cache),
                    jax.tree.leaves(solo._cache)):
        if a.ndim >= 2:
            a, b = (np.asarray(x[0, :depth], np.float32) for x in (a, b))
            assert np.abs(a - b).max() <= tol


def test_drain_finishes_the_running_and_leaves_nothing_in_flight(served):
    _, model, params, vocab = served
    prompts = _prompts(vocab, [6] * 6, seed=6)
    want, _ = _serve(model, params, prompts[:SLOTS], [8] * SLOTS,
                     sequential=True)
    eng = ServingEngine(model, params, **ENGINE)
    reqs = [eng.submit(p, 8) for p in prompts]
    for _ in range(3):
        eng.step()
    assert eng._flight and eng.active_slots == SLOTS
    assert eng.drain() == len(prompts) - SLOTS
    assert not eng._flight and not eng.has_work
    assert [list(map(int, r.tokens)) for r in reqs[:SLOTS]] == want
    assert all(r.reject_reason == "draining" for r in reqs[SLOTS:])


def test_a_parked_server_has_fetched_every_round(served):
    """The server parks only when ``has_work`` is false, and that is
    true while a round is unfetched: what a client got is complete when
    its request is done, and a request that wakes the loop finds the
    pipeline empty and is served like the first."""
    _, model, params, vocab = served
    prompts = _prompts(vocab, [5, 9], seed=7)
    want, _ = _serve(model, params, prompts, [7, 4], sequential=True)
    eng = ServingEngine(model, params, **ENGINE)
    srv = InferenceServer(eng).start()
    try:
        for _ in range(2):
            streams = [srv.stream(p, k) for p, k in zip(prompts, (7, 4))]
            got = [[int(t) for chunk in s for t in chunk] for s in streams]
            assert got == want
            deadline = time.monotonic() + 5.0
            while eng.has_work and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not eng.has_work and not eng._flight
    finally:
        srv.stop()
    assert not eng._flight


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_a_flipped_token_propagates_as_in_the_sequential_loop(
        tiny_llama, sampled):
    """The flip drill corrupts round 3's token of the first row after
    round 4 went out with the true one: that round is dropped and
    dispatched again on the wrong id, so the rest of the stream is what
    the one-round-at-a-time loop serves under the same drill (best-of-2
    branches and their logprobs included), and the other row's is
    untouched."""
    model, params = tiny_llama
    prompts = _prompts(97, [6, 9], seed=10)
    kw = dict(decode=DecodeSpec(temperature=0.8, best_of=2, n=2, seed=3)) \
        if sampled else {}

    def drilled(sequential, drill=True):
        chaos.reset()
        if drill:
            chaos.maybe_init("flip@replica=0:step=3", rank=0, seed=0)
        eng = ServingEngine(model, params, **{**ENGINE, "max_slots": 4})
        if sequential:
            _one_round_at_a_time(eng)
        reqs = [eng.submit(p, 9, **kw) for p in prompts]
        eng.run_until_idle()
        assert not eng._flight and not np.asarray(eng._d_slots[2]).any()
        if sampled:
            return [[(b["tokens"], b["logprob"]) for b in r.n_best]
                    for r in reqs]
        return [list(map(int, r.tokens)) for r in reqs]

    want, got, clean = drilled(True), drilled(False), drilled(False, False)
    assert got == want
    assert got[1] == clean[1]
    assert got[0] != clean[0]
    if not sampled:
        assert got[0][:3] == clean[0][:3] and got[0][3] != clean[0][3]


def _instrument(monkeypatch):
    """The order of decode dispatches and of their tokens' fetches:
    ``[("dispatch", n), ("fetch", n), ...]``, rounds counted from 1."""
    log, rounds = [], {}
    step = engine_mod._serve_step

    def dispatching(*args):
        out = step(*args)
        rounds[id(out[0])] = len(rounds) + 1
        log.append(("dispatch", rounds[id(out[0])]))
        dispatching.keep.append(out[0])     # ids stay unique
        return out
    dispatching.keep = []

    class Numpy:
        """``engine.np`` with the fetch of a round's tokens logged."""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *a, **kw):
            if id(x) in rounds:
                log.append(("fetch", rounds[id(x)]))
            return np.asarray(x, *a, **kw)

    monkeypatch.setattr(engine_mod, "_serve_step", dispatching)
    monkeypatch.setattr(engine_mod, "np", Numpy())
    return log


def test_round_n_plus_1_is_dispatched_before_round_n_is_fetched(
        tiny_llama, monkeypatch):
    """Steady state by the call order: every round but a batch's last
    is followed by the next round's dispatch before its own fetch; each
    call of ``_decode_round`` fetches exactly one round, in order; and
    the overlap counter reads what the call order says."""
    model, params = tiny_llama
    log = _instrument(monkeypatch)
    eng = ServingEngine(model, params, **ENGINE)
    calls = []
    inner = eng._decode_round
    eng._decode_round = lambda: (calls.append(len(log)), inner())[1]
    prompts = _prompts(97, [5, 9, 4, 7], seed=8)
    reqs = [eng.submit(p, k) for p, k in zip(prompts, (9, 3, 6, 5))]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    fetched = [n for what, n in log if what == "fetch"]
    dispatched = [n for what, n in log if what == "dispatch"]
    assert fetched == dispatched == list(range(1, len(fetched) + 1))
    assert len(eng.round_seconds) == len(fetched) == len(calls)
    for lo, hi in zip(calls, calls[1:] + [len(log)]):
        assert [w for w, _ in log[lo:hi]].count("fetch") == 1
    # rounds dispatched while the round before them was unfetched
    at = {ev: i for i, ev in enumerate(log)}
    ahead = [n for n in dispatched[1:]
             if at[("dispatch", n)] < at[("fetch", n - 1)]]
    # the longest request decodes 8 rounds: all of them but the first,
    # and every round of the others inside them, went out ahead
    assert len(ahead) >= 7
    assert len(ahead) >= len(dispatched) - 2
    assert eng.summary()["rounds_overlapped"] == len(ahead)
    assert obs.get_registry().counter(
        "serve_rounds_overlapped_total").value() == len(ahead)


def test_round_seconds_run_from_the_previous_fetch_not_the_dispatch(
        tiny_llama):
    """A round dispatched ahead is timed from when the round before it
    was fetched: with the host asleep 30 ms in every round (``slow@``),
    a round's entry holds one sleep, not the two between its dispatch
    and its fetch."""
    model, params = tiny_llama
    eng = ServingEngine(model, params, **ENGINE)
    eng.warmup((8,))
    chaos.maybe_init("slow@rank=0:ms=30", rank=0, seed=0)
    (p,) = _prompts(97, [6], seed=9)
    eng.submit(p, 6)
    eng.run_until_idle()
    assert eng.summary()["rounds_overlapped"] == 4
    assert len(eng.round_seconds) == 5
    assert all(0.03 <= dt < 0.055 for dt in eng.round_seconds), \
        eng.round_seconds
