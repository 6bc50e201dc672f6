"""The serve loop's spans (ISSUE 27): what ``obs.span`` does with no
sink, with the recorder, and with a running ``jax.profiler`` session;
which spans a tiny engine writes into the session's ``.xplane.pb`` and
how they nest; and the passivity pin: the spans move no device work.

The profiler session here is the CPU profiler with the options the
benchmark's traced run uses (``benchmark/run.py``'s ``Tracer``:
``host_tracer_level`` 1, Python tracer off).
"""

import glob
import importlib
import inspect
import sys
import threading
import time

import jax
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import InferenceServer, ServingEngine

# the package exports the function under the module's name
span_mod = importlib.import_module("pytorch_distributed_nn_tpu.obs.span")

VOCAB = 97
# prompt lengths and budgets of the fixed set of requests every test
# here drives: a short and a two-block prompt together, then the long
# one again once it retired (its first block comes from the prefix cache)
REQUESTS = ((5, 3), (19, 2))

# each span's parent on its thread (the span that directly encloses it)
PARENTS = {
    "serve/round": (None,),
    "serve/next_admissions": ("serve/round",),
    "serve/prefix_match": ("serve/next_admissions",),
    "serve/admit": ("serve/round",),
    "serve/prefill_into": ("serve/admit",),
    "serve/fresh_cache": ("serve/prefill_into",),
    "serve/restore": ("serve/prefill_into",),
    "serve/prefill": ("serve/prefill_into",),
    "serve/insert_row": ("serve/prefill_into",),
    "serve/decode": ("serve/round",),
    "serve/round_host": ("serve/round",),
    "serve/retire": ("serve/admit", "serve/round_host"),
    "serve/save_blocks": ("serve/retire",),
    "serve/release": ("serve/retire",),
}


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    chaos.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    obs.disable_tracing()
    yield
    obs.disable_tracing()


def _drive(model, params):
    """The fixed set of requests through a fresh engine, on this
    thread. Returns the tokens each request got."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, size=(n,)).astype(np.int32)
               for n, _ in REQUESTS]
    eng = ServingEngine(model, params, max_slots=4, max_seq_len=64,
                        block_size=16)
    reqs = [eng.submit(p, k) for p, (_, k) in zip(prompts, REQUESTS)]
    eng.run_until_idle()
    reqs.append(eng.submit(prompts[1], 2))
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return [list(map(int, r.tokens)) for r in reqs]


class _Session:
    """A profiler session as the benchmark's traced run starts one."""

    def __init__(self, path):
        self.path = str(path)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def lines(self):
        """``[[(name, start_ns, end_ns, stats), ...], ...]``: the
        events of each thread of the ``/host:CPU`` plane, by start."""
        from jax.profiler import ProfileData

        [pb] = glob.glob(self.path + "/plugins/profile/*/*.xplane.pb")
        out = []
        for plane in ProfileData.from_file(pb).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events]
                out.append(sorted(evs, key=lambda ev: (ev[1], -ev[2])))
        return out


def _programs(line):
    """The jitted programs a thread dispatched, in order. The runtime
    writes ``PjitFunction(<name>)`` twice per dispatch, one inside the
    other; the outer one is kept."""
    out, inner_until = [], -1.0
    for name, s, e, _ in line:
        if not name.startswith("PjitFunction("):
            continue
        if s < inner_until:
            continue
        inner_until = e
        out.append(name[len("PjitFunction("):-1])
    return out


def _line_with(lines, prefix):
    """The one thread whose line holds events named ``prefix*``."""
    have = [ln for ln in lines if any(n.startswith(prefix)
                                      for n, *_ in ln)]
    assert len(have) == 1, f"{len(have)} threads hold {prefix}* events"
    return have[0]


def _with_parents(line):
    """``[(name, stats, parent name or None)]`` of the ``serve/*``
    events of one thread, from interval containment."""
    out, stack = [], []
    for name, s, e, stats in line:
        if not name.startswith("serve/"):
            continue
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            assert e <= stack[-1][2], (
                f"{name} [{s}, {e}] straddles the end of "
                f"{stack[-1][0]} at {stack[-1][2]}")
        out.append((name, stats, stack[-1][0] if stack else None))
        stack.append((name, s, e))
    return out


@pytest.fixture(scope="module")
def traced(tiny_llama, tmp_path_factory):
    """The fixed requests under a profiler session: the engine thread's
    events, and those of a server that waited."""
    model, params = tiny_llama
    _drive(model, params)   # compile outside the session
    with _Session(tmp_path_factory.mktemp("spans")) as sess:
        tokens = _drive(model, params)
        server = InferenceServer(
            ServingEngine(model, params, max_slots=2, max_seq_len=32),
            idle_wait_s=0.002).start()
        time.sleep(0.05)
        server.stop(timeout=30.0)
    lines = sess.lines()
    return dict(tokens=tokens, engine=_line_with(lines, "serve/round"),
                server=_line_with(lines, "serve/parked"))


# -- the span itself ------------------------------------------------------

def test_unarmed_span_is_the_shared_null_context_and_allocates_nothing():
    assert not obs.tracing_enabled()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s1 = obs.span("x")
    s2 = obs.span("serve/round", cat="serve", round=3)
    assert s1 is s2
    with s1 as inside:
        inside.set(occ=4)   # dropped

    def loop(n):
        for i in range(n):
            with obs.span("serve/round", round=3) as sp:
                sp.set(occ=1)

    loop(100)
    before = sys.getallocatedblocks()
    loop(10_000)
    assert sys.getallocatedblocks() - before < 50


def test_recorder_sink_records_as_before_and_takes_late_args():
    rec = obs.enable_tracing(process_index=0)
    with obs.span("serve/retire", cat="serve") as sp:
        with obs.span("inner", step=1):
            pass
        sp.set(n=2)
    assert obs.disable_tracing() is rec
    evs = {e["name"]: e for e in rec.events()}
    assert evs["serve/retire"]["args"] == {"n": 2}
    assert evs["serve/retire"]["cat"] == "serve"
    assert evs["inner"]["args"] == {"step": 1}
    outer, inner = evs["serve/retire"], evs["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert obs.span("after") is obs.span("again")


def test_both_sinks_at_once(tmp_path):
    rec = obs.enable_tracing(process_index=0)
    with _Session(tmp_path) as sess:
        with obs.span("both/sinks", k=7) as sp:
            sp.set(late=1)
    obs.disable_tracing()
    [ev] = [e for e in rec.events() if e["name"] == "both/sinks"]
    assert ev["args"] == {"k": 7, "late": 1}
    [line] = [ln for ln in sess.lines()
              if any(n == "both/sinks" for n, *_ in ln)]
    [stats] = [st for n, _, _, st in line if n == "both/sinks"]
    assert stats == {"k": 7, "late": 1}


def test_span_module_imports_no_jax():
    src = inspect.getsource(span_mod)
    assert "import jax" not in src and "from jax" not in src


# -- the serve loop's spans in the profiler's trace -------------------------

@pytest.mark.parametrize("name", sorted(PARENTS))
def test_engine_span_in_profiler_trace(traced, name):
    """Each span is on the engine thread's line, inside the span the
    table names, with its arguments readable as stats."""
    rows = [(n, st, parent) for n, st, parent
            in _with_parents(traced["engine"]) if n == name]
    assert rows, f"no {name} event on the engine thread's line"
    for _, stats, parent in rows:
        assert parent in PARENTS[name], (name, parent)
    stats = [st for _, st, _ in rows]
    if name == "serve/round":
        assert [st["round"] for st in stats] == \
            list(range(1, len(stats) + 1))
        assert all(0 <= st["occ"] <= 4 for st in stats)
        assert stats[0]["occ"] == 1     # two admitted, one retired
    elif name == "serve/next_admissions":
        # opened whether or not a request is admitted, closed before
        # serve/admit opens; the three admissions are two passes
        assert [st["admitted"] for st in stats if st["admitted"]] == [2, 1]
        assert len(stats) > 2
        assert all(st["queued"] >= st["admitted"] and st["lock_wait_us"] >= 0
                   for st in stats)
        ends = [e for n, _, e, _ in traced["engine"]
                if n == "serve/next_admissions"]
        admits = [s for n, s, _, _ in traced["engine"]
                  if n == "serve/admit"]
        assert all(any(e <= a for e in ends) for a in admits)
    elif name == "serve/prefix_match":
        # one a reservation; the third request shares its first block
        assert [st["blocks"] for st in stats] == [0, 0, 1]
    elif name == "serve/release":
        # full blocks the retiring sequence donates: 19 + 2 - 1 rows
        # (the two-token budget ends first), 5 + 3 - 1, 19 + 2 - 1
        assert [st["blocks"] for st in stats] == [1, 0, 1]
    elif name == "serve/save_blocks":
        # the dispatch of the copy ahead of a release that has blocks
        assert [st["blocks"] for st in stats] == [1, 1]
    elif name == "serve/decode":
        # the host's part of the call, before it waits for the chip
        assert all(0 <= st["dispatch_us"] for st in stats)
    elif name == "serve/admit":
        assert [st["n"] for st in stats] == [2, 1]
    elif name == "serve/prefill_into":
        assert [(st["tokens"], st["padded"], st["cached"], st["row_len"])
                for st in stats] == [(5, 16, 0, 16), (19, 32, 0, 32),
                                     (3, 16, 16, 32)]
        assert all(st["request"].startswith("req-") for st in stats)
        assert len({st["request"] for st in stats}) == 3
    elif name == "serve/restore":
        assert [st["blocks"] for st in stats] == [1]
    elif name == "serve/prefill":
        into = [st["request"] for n, st, _
                in _with_parents(traced["engine"])
                if n == "serve/prefill_into"]
        assert [st["request"] for st in stats] == into
    elif name == "serve/insert_row":
        assert [st["rows"] for st in stats] == [1, 1, 1]
    elif name == "serve/round_host":
        assert sum(st["retired"] for st in stats) == 3
    elif name == "serve/retire":
        assert sum(st["n"] for st in stats) == 3
        assert {p for _, _, p in rows} == {"serve/admit",
                                           "serve/round_host"}


def test_parked_span_for_a_server_that_waits(traced):
    parked = [(s, e) for n, s, e, _ in traced["server"]
              if n == "serve/parked"]
    assert len(parked) >= 3
    # an idle wait of 2 ms, give or take the host's scheduling
    assert all(e - s > 1e6 for s, e in parked[:-1])
    assert not any(n == "serve/round" for n, *_ in traced["server"])


def test_spans_of_one_request_share_its_id(traced):
    rows = _with_parents(traced["engine"])
    by_req = {}
    for n, st, _ in rows:
        if "request" in st:
            by_req.setdefault(st["request"], []).append(n)
    assert len(by_req) == 3
    assert all(v == ["serve/prefill_into", "serve/prefill"]
               for v in by_req.values())


# -- passivity --------------------------------------------------------------

# The programs the engine thread dispatches for REQUESTS, from building
# the engine to the last retire; C is the eager ``convert_element_type``
# (``jnp.asarray``) and B the eager ``broadcast_in_dim`` (``jnp.zeros``).
# A span that moved, added or renamed a device program, or an eager
# operation, changes this list. The first line is the constructor's:
# the batch cache, then the prefix store, the slot state, the stop
# token and the sampling mirrors of an engine without a bank. Each
# admission takes its zeroed row from one ``_zero_cache``; the two ``C``
# before a prefill are its ``jnp.asarray`` uploads; one ``_write_rows``
# an admission pass sets the slot state of the rows it filled. The loop
# keeps a round in flight (ISSUE 32): the second ``_serve_step`` is
# dispatched before the first one's tokens are fetched, and the short
# request's retire (``_save_blocks``) follows both.
PINNED_DISPATCHES = (
    "_zero_cache C C B C B C C B C B C B C B C B C B C "
    "_zero_cache C C _serve_prefill _insert_row "
    "_zero_cache C C _serve_prefill _insert_row _write_rows "
    "_serve_step _serve_step _save_blocks "
    "_zero_cache _restore_blocks C C _serve_prefill _insert_row _write_rows "
    "_serve_step _save_blocks "
).split()
_SHORT = {"convert_element_type": "C", "broadcast_in_dim": "B"}


def _dispatches(tiny_llama, tmp_path):
    model, params = tiny_llama
    _drive(model, params)
    with _Session(tmp_path) as sess:
        tokens = _drive(model, params)
    line = max(sess.lines(), key=lambda ln: len(_programs(ln)))
    return tokens, line


def test_spans_dispatch_the_parents_programs_armed_and_unarmed(
        tiny_llama, tmp_path, monkeypatch):
    """Same programs, same order, same tokens: with the spans written
    to the session, with the spans unarmed, and as pinned."""
    tokens_a, armed = _dispatches(tiny_llama, tmp_path / "armed")
    assert any(n == "serve/decode" for n, *_ in armed)
    # unarmed: the session still runs (it is the instrument that lists
    # the dispatches) but the span finds no sink
    monkeypatch.setattr(span_mod, "_session_annotation", lambda: None)
    tokens_u, unarmed = _dispatches(tiny_llama, tmp_path / "unarmed")
    assert not any(n.startswith("serve/") for n, *_ in unarmed)
    assert tokens_a == tokens_u
    assert _programs(armed) == _programs(unarmed)
    assert [_SHORT.get(n, n) for n in _programs(unarmed)] \
        == PINNED_DISPATCHES


def test_admission_mints_its_row_cache_in_one_dispatch(traced):
    """ISSUE 28: from the engine's first round to its last retire no
    eager ``jnp.zeros`` runs, and every prefill takes its zeroed row
    cache from exactly one ``_zero_cache`` dispatch, under its
    ``serve/fresh_cache`` span."""
    line = traced["engine"]
    first = min(s for n, s, _, _ in line if n == "serve/round")
    last = max(e for n, _, e, _ in line if n == "serve/retire")

    def inside(lo, hi):
        return _programs([ev for ev in line if lo <= ev[1] and ev[2] <= hi])

    live = inside(first, last)
    assert "_serve_prefill" in live and "_restore_blocks" in live
    assert "broadcast_in_dim" not in live
    spans = {name: [(s, e) for n, s, e, _ in line if n == name]
             for name in ("serve/prefill_into", "serve/fresh_cache")}
    assert len(spans["serve/prefill_into"]) == 3
    for s, e in spans["serve/prefill_into"]:
        assert inside(s, e).count("_zero_cache") == 1
    assert [inside(s, e) for s, e in spans["serve/fresh_cache"]] \
        == [["_zero_cache"]] * 3
    assert live.count("_zero_cache") == 3


def test_decode_round_is_the_parents_byte_for_byte():
    """The hot loop holds no span and one dispatch site, of the one
    step program: a call runs it until a round is in flight beyond the
    one it fetches (once in steady state). What that program is for a
    greedy batch is read off its lowered text (test_quality.py), not
    off this source."""
    src = inspect.getsource(ServingEngine._decode_round)
    assert "obs.span" not in src and ".phase(" not in src
    assert src.count("_serve_step(") == 1
    assert src.count("np.asarray(") == 1   # one fetch
    assert src.count("_write_rows(") == src.count("_insert_row(") == 0


def test_unarmed_spans_of_a_decode_round_cost_under_20_us():
    """A plain decode round enters five spans (round, next_admissions,
    decode, round_host, retire), three of them through the loop's
    tally, which also splits the decode call where the wait begins,
    sets seven late arguments and leaves one round record."""
    from pytorch_distributed_nn_tpu.obs import goodput

    tally = goodput.GoodputMeter(
        goodput.SERVE_PHASES, goodput.SERVE_SPANS, clock=time.monotonic,
        rounds=True)
    tally.start()

    def rounds(n):
        for i in range(n):
            with obs.span("serve/round", round=i) as rnd:
                with tally.phase("next_admissions") as nxt:
                    nxt.set(queued=0, admitted=0, lock_wait_us=0)
                with tally.phase("dispatch") as dec:
                    dec.set(dispatch_us=dec.split("fetch",
                                                  time.monotonic()))
                with tally.phase("round_host") as host:
                    with obs.span("serve/retire") as sp:
                        sp.set(n=0)
                    host.set(retired=0)
                tally.lap(i, occ=3)
                rnd.set(occ=3)

    rounds(1000)
    # the best of many short stretches: one of 500 rounds is shorter
    # than a scheduler's slice, so some run undisturbed beside the other
    # workers of a loaded test machine
    best = float("inf")
    for _ in range(50):
        t = time.perf_counter()
        rounds(500)
        best = min(best, (time.perf_counter() - t) / 500)
    assert best < 20e-6, f"{best * 1e6:.1f} us a round"


def test_span_threads_keep_their_own_lines(tmp_path):
    def worker():
        with obs.span("on/worker"):
            pass

    with _Session(tmp_path) as sess:
        with obs.span("on/main"):
            th = threading.Thread(target=worker)
            th.start()
            th.join(10.0)
            assert not th.is_alive()
    lines = sess.lines()
    main = _line_with(lines, "on/main")
    worker = _line_with(lines, "on/worker")
    assert main is not worker
