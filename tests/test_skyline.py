"""Skyline traffic generator + capacity frontier (ISSUE 11 tentpole).

Covers the spec grammar's loud-failure contract, the byte-identical
trace determinism the replay tooling depends on, the deterministic
service model (including the ``kill_replica@`` chaos drill moving the
frontier and naming its failover window), the watchtower-judged rung
verdicts, and the satellites: ``Histogram.quantile`` edge cases, the
seeded-poisson ``arrival_offsets`` schedule, and the ``obs.stats``
helpers on heavy-tailed and NaN-contaminated inputs.
"""

import json
import math
import random

import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.obs import capacity, stats
from pytorch_distributed_nn_tpu.obs.registry import Histogram
from pytorch_distributed_nn_tpu.serve import traffic
from pytorch_distributed_nn_tpu.serve.server import arrival_offsets

SPEC = ("diurnal@rps=6:duration_s=8:amplitude=0.5:period_s=8;"
        "flash@at_s=4:peak=3:ramp_s=1:hold_s=1;"
        "tenant@name=chat:weight=3:prompt_med=12:prompt_sigma=0.5"
        ":prompt_max=40:out_med=8:out_max=16;"
        "tenant@name=batch:weight=1:prompt=zipf:prompt_a=1.5"
        ":prompt_max=40:out_med=12:out_max=16")


# the capacity drill's: a shorter diurnal, the flash crowd holding
# over t=3..4
DRILL_SPEC = ("diurnal@rps=4:duration_s=6:amplitude=0.5:period_s=6;"
              "flash@at_s=3:peak=3:ramp_s=1:hold_s=1;"
              "tenant@name=chat:weight=3:prompt_med=12:prompt_sigma=0.5"
              ":prompt_max=40:out_med=8:out_sigma=0.4:out_max=16;"
              "tenant@name=batch:weight=1:prompt=zipf:prompt_a=1.5"
              ":prompt_max=40:out_med=12:out_max=16")
# (spec, seed) and, for the chaos drill, the mid-flash-crowd second
# at which replica 0 dies
_SEEDED = [pytest.param(SPEC, 3, id="spec"),
           pytest.param(DRILL_SPEC, 7, id="drill")]
_KILLED = [pytest.param(SPEC, 3, 4.5, id="spec"),
           pytest.param(DRILL_SPEC, 7, 3.5, id="drill")]


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset_registry()
    yield


# ---------------------------------------------------------------------------
# Spec grammar
# ---------------------------------------------------------------------------


def test_parse_roundtrip_describe():
    spec = traffic.parse_spec(SPEC)
    assert spec.base.kind == "diurnal"
    assert spec.base_rps == 6.0
    assert spec.duration_s == 8.0
    assert spec.shape_name == "diurnal+flash"
    assert [t.args["name"] for t in spec.tenants] == ["chat", "batch"]
    # describe() is itself a parseable spec (canonical form)
    again = traffic.parse_spec(spec.describe())
    assert again.describe() == spec.describe()


@pytest.mark.parametrize("bad,frag", [
    ("tsunami@rps=1", "unknown traffic shape"),
    ("steady@rps=1:wavelength=3", "unknown traffic key"),
    ("steady@rps=banana", "bad value"),
    ("steady@rps", "malformed traffic field"),
    ("flash@at_s=1:peak=2", "exactly one base envelope"),
    ("steady@rps=2;diurnal@rps=3", "exactly one base envelope"),
    ("steady@rps=0", "rps must be > 0"),
    ("diurnal@rps=1:amplitude=1.5", "amplitude must be in"),
    ("steady@rps=1;tenant@name=x:prompt_a=0.9", "must be > 1"),
    ("steady@rps=1;tenant@name=x:prompt_min=9:prompt_max=4",
     "prompt_min <= prompt_max"),
    ("steady@rps=1;tenant@name=x:prompt=uniform", "must be one of"),
    ("steady@rps=1;tenant@name=x:prefix_len=-1",
     "prefix_len must be >= 0"),
    ("steady@rps=1;tenant@name=x:prefix_len=8:n_prefixes=0",
     "n_prefixes must be >= 1"),
    ("steady@rps=1;tenant@name=x:n_prefixes=3",
     "n_prefixes without prefix_len"),
    ("steady@rps=1;tenant@name=x:prefix_len=3.5", "bad value"),
])
def test_parse_rejects_loudly(bad, frag):
    with pytest.raises(ValueError, match=frag):
        traffic.parse_spec(bad)


def test_maybe_from_env(monkeypatch):
    monkeypatch.delenv(traffic.ENV_TRAFFIC, raising=False)
    assert traffic.maybe_from_env() is None
    monkeypatch.setenv(traffic.ENV_TRAFFIC, "0")
    assert traffic.maybe_from_env() is None
    monkeypatch.setenv(traffic.ENV_TRAFFIC, "steady@rps=2")
    assert traffic.maybe_from_env().base_rps == 2.0


# ---------------------------------------------------------------------------
# Trace determinism + serialization
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("text, seed", _SEEDED)
def test_trace_byte_identical_per_seed(text, seed):
    spec = traffic.parse_spec(text)
    t1 = traffic.generate_trace(spec, seed=seed)
    t2 = traffic.generate_trace(spec, seed=seed)
    assert traffic.trace_to_jsonl(t1) == traffic.trace_to_jsonl(t2), \
        "trace JSONL not byte-identical for same spec+seed"
    assert t1 and {r["tenant"] for r in t1} == {"chat", "batch"}, \
        f"tenant mix missing: {len(t1)} requests"
    c = traffic.trace_to_jsonl(traffic.generate_trace(spec, seed=seed + 1))
    assert c != traffic.trace_to_jsonl(t1)


def test_trace_shape_and_scaling():
    spec = traffic.parse_spec(SPEC)
    trace = traffic.generate_trace(spec, seed=3)
    assert {r["tenant"] for r in trace} == {"chat", "batch"}
    assert all(0.0 <= r["t"] < spec.duration_s for r in trace)
    assert all(1 <= r["prompt_len"] <= 40 for r in trace)
    assert all(1 <= r["max_new"] <= 16 for r in trace)
    ts = [r["t"] for r in trace]
    assert ts == sorted(ts)
    assert [r["i"] for r in trace] == list(range(len(trace)))
    # the rps_scale knob actually scales offered load
    big = traffic.generate_trace(spec, seed=3, rps_scale=4.0)
    assert len(big) > 2 * len(trace)


def test_trace_jsonl_roundtrip(tmp_path):
    spec = traffic.parse_spec(SPEC)
    trace = traffic.generate_trace(spec, seed=3)
    path = tmp_path / "trace.jsonl"
    traffic.write_trace(str(path), trace)
    assert traffic.load_trace(str(path)) == trace
    # canonical form: every line is sort_keys JSON
    for line in path.read_text().splitlines():
        assert line == json.dumps(json.loads(line), sort_keys=True)


def test_prompt_tokens_derived_not_stored():
    spec = traffic.parse_spec(SPEC)
    rec = traffic.generate_trace(spec, seed=3)[0]
    a = traffic.prompt_tokens(rec, vocab_size=97)
    b = traffic.prompt_tokens(rec, vocab_size=97)
    assert (a == b).all()
    assert a.shape == (rec["prompt_len"],)
    assert a.min() >= 0 and a.max() < 97


PREFIX_SPEC = ("steady@rps=40:duration_s=2;"
               "tenant@name=chat:prefix_len=24:n_prefixes=2"
               ":prompt_med=40:prompt_max=64;"
               "tenant@name=batch:prompt_med=12:prompt_max=24")


def test_prefix_tenant_records_and_shared_tokens():
    spec = traffic.parse_spec(PREFIX_SPEC)
    trace = traffic.generate_trace(spec, seed=5)
    chat = [r for r in trace if r["tenant"] == "chat"]
    batch = [r for r in trace if r["tenant"] == "batch"]
    assert chat and batch
    # prefix fields only on the prefix tenant; prompt always extends
    # past its prefix
    assert all("prefix_seed" not in r for r in batch)
    assert all(r["prefix_len"] == 24 for r in chat)
    assert all(r["prompt_len"] >= 25 for r in chat)
    # n_prefixes=2 distinct pools, both actually drawn at this volume
    seeds = {r["prefix_seed"] for r in chat}
    assert len(seeds) == 2
    # same prefix_seed -> byte-identical leading 24 tokens, distinct
    # suffixes; different prefix_seed -> different prefix
    by_seed: dict = {}
    for r in chat:
        by_seed.setdefault(r["prefix_seed"], []).append(
            traffic.prompt_tokens(r, vocab_size=97))
    for toks in by_seed.values():
        assert all((t[:24] == toks[0][:24]).all() for t in toks)
    a, b = (v[0] for v in list(by_seed.values())[:2])
    assert not (a[:24] == b[:24]).all()
    # determinism: the spec+seed contract holds with prefix tenants
    again = traffic.trace_to_jsonl(traffic.generate_trace(spec, seed=5))
    assert again == traffic.trace_to_jsonl(trace)


def test_replay_preserves_order_and_budgets():
    spec = traffic.parse_spec(SPEC)
    trace = traffic.generate_trace(spec, seed=3)
    seen = []
    handles = traffic.replay_trace(
        trace, lambda p, n: seen.append((len(p), n)) or len(seen),
        vocab_size=97, realtime=False)
    assert handles == list(range(1, len(trace) + 1))
    assert [n for _, n in seen] == [r["max_new"] for r in trace]
    assert [p for p, _ in seen] == [r["prompt_len"] for r in trace]


DECODE_SPEC = ("steady@rps=40:duration_s=2;"
               "tenant@name=sampler:temperature=0.8:n=3"
               ":prompt_med=12:prompt_max=24;"
               "tenant@name=streamer:stream=0.5"
               ":prompt_med=12:prompt_max=24;"
               "tenant@name=plain:prompt_med=12:prompt_max=24")


def test_decode_tenant_records_and_determinism():
    """ISSUE 20: Prism decode keys in the grammar. Key-absent wire
    discipline (only tenants that set them emit them), decode_seed
    derived arithmetically per record (no extra rng draw — non-decode
    tenants are untouched), the seeded stream= coin is deterministic,
    and the spec+seed byte-identity contract holds with the new
    keys."""
    spec = traffic.parse_spec(DECODE_SPEC)
    trace = traffic.generate_trace(spec, seed=6)
    samp = [r for r in trace if r["tenant"] == "sampler"]
    strm = [r for r in trace if r["tenant"] == "streamer"]
    plain = [r for r in trace if r["tenant"] == "plain"]
    assert samp and strm and plain
    decode_keys = {"temperature", "n", "decode_seed", "stream"}
    for r in plain:
        assert not (decode_keys & set(r))
    for r in samp:
        assert r["temperature"] == 0.8 and r["n"] == 3
        assert 0 <= r["decode_seed"] < 2 ** 31
        assert "stream" not in r
    # per-record arithmetic derivation: all distinct, no collisions
    assert len({r["decode_seed"] for r in samp}) == len(samp)
    flags = [r.get("stream", False) for r in strm]
    assert any(flags) and not all(flags)  # the 0.5 mix actually mixes
    assert all("decode_seed" not in r for r in strm)
    again = traffic.trace_to_jsonl(traffic.generate_trace(spec, seed=6))
    assert again == traffic.trace_to_jsonl(trace)


@pytest.mark.parametrize("bad, frag", [
    ("steady@rps=1;tenant@name=x:temperature=-0.5",
     "temperature must be >= 0"),
    ("steady@rps=1;tenant@name=x:n=0", "n must be >= 1"),
    ("steady@rps=1;tenant@name=x:stream=1.5", "probability"),
    ("steady@rps=1;tenant@name=x:stream=0.5:n=2", "n-best"),
    ("steady@rps=1;tenant@name=x:nbest=2", "unknown"),
])
def test_decode_keys_reject_loudly(bad, frag):
    with pytest.raises(ValueError, match=frag):
        traffic.parse_spec(bad)


def test_replay_passes_decode_kwargs_and_spares_plain_adapters():
    """Decode-carrying records submit with decode=/stream= kwargs;
    records without them go through the plain two-argument call, so a
    pre-Prism ``lambda p, n`` adapter replays old traces unchanged."""
    from pytorch_distributed_nn_tpu.serve.decoding import DecodeSpec

    spec = traffic.parse_spec(DECODE_SPEC)
    trace = traffic.generate_trace(spec, seed=6)
    calls = []

    def submit(p, n, **kw):
        calls.append((len(p), n, kw))
        return len(calls)

    traffic.replay_trace(trace, submit, vocab_size=97, realtime=False)
    assert len(calls) == len(trace)
    for rec, (_, _, kw) in zip(trace, calls):
        if rec["tenant"] == "sampler":
            assert kw["decode"] == DecodeSpec(
                temperature=0.8, n=3, seed=rec["decode_seed"])
        elif rec["tenant"] == "streamer":
            assert kw == ({"stream": True} if rec.get("stream")
                          else {})
        else:
            assert kw == {}
    # plain records never see kwargs at all: a 2-arg lambda suffices
    plain_only = [r for r in trace if r["tenant"] == "plain"]
    handles = traffic.replay_trace(
        plain_only, lambda p, n: True, vocab_size=97, realtime=False)
    assert all(handles)


def test_trace_without_decode_keys_is_unchanged():
    """Adding the decode grammar must not move a byte of any existing
    spec's trace: tenants without the keys draw from the same rng
    stream in the same order (the prefix_len precedent)."""
    base = traffic.generate_trace(traffic.parse_spec(SPEC), seed=3)
    assert all("decode_seed" not in r and "stream" not in r
               for r in base)
    # the same tenants with decode keys added produce the SAME
    # arrival/prompt/budget skeleton — decode keys only annotate
    decorated_spec = SPEC.replace(
        "tenant@name=chat:weight=3",
        "tenant@name=chat:temperature=0.7:weight=3")
    deco = traffic.generate_trace(traffic.parse_spec(decorated_spec),
                                  seed=3)
    strip = {"temperature", "n", "decode_seed", "stream"}
    assert [{k: v for k, v in r.items() if k not in strip}
            for r in deco] == base


# ---------------------------------------------------------------------------
# Service model + judge
# ---------------------------------------------------------------------------


def _sim(spec, n, **kw):
    trace = traffic.generate_trace(spec, seed=3)
    return capacity.simulate_fleet(trace, replicas=n,
                                   duration_s=spec.duration_s, **kw)


def test_simulate_fleet_light_load_sustains():
    spec = traffic.parse_spec(SPEC)
    run = _sim(spec, 2)
    assert run["rejects"] == 0
    assert run["goodput_tps"] > 0
    verdict = capacity.judge_rung(
        run["events"], slo=capacity.DEFAULT_SLOS[0],
        duration_s=spec.duration_s)
    assert verdict["sustainable"] and verdict["burn_pages"] == 0


def test_simulate_fleet_overload_sheds_and_burns():
    spec = traffic.parse_spec(SPEC)
    trace = traffic.generate_trace(spec, seed=3, rps_scale=8.0)
    run = capacity.simulate_fleet(trace, replicas=1, slots=1,
                                  decode_tps=20.0,
                                  duration_s=spec.duration_s)
    assert run["rejects"] > 0
    verdict = capacity.judge_rung(
        run["events"], slo=capacity.DEFAULT_SLOS[0],
        duration_s=spec.duration_s)
    assert not verdict["sustainable"]


def test_chaos_kill_names_failover_window():
    spec = traffic.parse_spec(SPEC)
    kill = "kill_replica@replica=0:after_s=4.5"  # mid-flash-crowd
    run = _sim(spec, 2, chaos_spec=kill)
    downs = [e for e in run["events"] if e["ev"] == "replica_down"]
    assert len(downs) == 1 and downs[0]["t"] == 4.5
    wins = run["failover_windows"]
    assert wins and wins[0]["replica"] == 0
    assert wins[0]["t_down"] == 4.5
    if wins[0]["readmitted"]:
        assert wins[0]["t_recovered"] > 4.5
    # the kill is deterministic too
    again = _sim(spec, 2, chaos_spec=kill)
    assert again["failover_windows"] == wins


def test_kill_all_replicas_rejects_everything_after():
    spec = traffic.parse_spec(SPEC)
    run = _sim(spec, 1, chaos_spec="kill_replica@replica=0:after_s=2")
    reasons = {e["reason"] for e in run["events"]
               if e["ev"] == "serve_reject"}
    assert "no_replicas" in reasons
    late = [e for e in run["events"]
            if e["ev"] == "serve_request" and e["t"] > 2.0
            and not e["failovers"]]
    # nothing newly arriving after the kill completes
    assert all(e["t"] <= 2.0 or e["failovers"] for e in
               (e for e in run["events"] if e["ev"] == "serve_request")
               ) or not late


def _plan(spec, seed, kill=None, replica_counts=(1, 2)):
    # slots=2/decode_tps=60: tight enough that losing 1 of 2 replicas
    # actually drops the frontier a rung (not just reshapes the window)
    return capacity.plan_capacity(
        spec, make_run_rung=lambda n: capacity.simulated_run_rung(
            n, slots=2, decode_tps=60.0, chaos_spec=kill),
        chaos_spec=kill, replica_counts=replica_counts,
        rates=(0.5, 1.0, 2.0, 4.0), seed=seed)


@pytest.mark.parametrize("text, seed", _SEEDED)
def test_plan_capacity_report_identical_twice(text, seed):
    spec = traffic.parse_spec(text)
    rep_a = _plan(spec, seed)
    obs.reset_registry()  # gauges re-register; report must not care
    rep_b = _plan(spec, seed)
    assert (capacity.report_to_json(rep_a)
            == capacity.report_to_json(rep_b)), \
        "capacity report not identical twice in a row"
    assert rep_a["replicas_needed"]  # the headline table exists
    kinds = {e["event"] for e in capacity.report_events(rep_a)}
    assert kinds == {"capacity_rung", "capacity_frontier",
                     "capacity_plan"}


@pytest.mark.parametrize("text, seed, kill_s", _KILLED)
def test_chaos_drill_moves_frontier(text, seed, kill_s):
    spec = traffic.parse_spec(text)
    kill = f"kill_replica@replica=0:after_s={kill_s}"
    rep_a = _plan(spec, seed, replica_counts=(2,))
    rep_k = _plan(spec, seed, kill, replica_counts=(2,))
    assert (rep_k["sweeps"]["2"]["frontier"]
            != rep_a["sweeps"]["2"]["frontier"]), \
        "chaos drill did not move the 2-replica frontier"
    f_calm = rep_a["sweeps"]["2"]["frontier"]["interactive"]
    f_kill = rep_k["sweeps"]["2"]["frontier"]["interactive"]
    assert (f_kill or 0.0) < f_calm
    assert rep_k["chaos"] == kill
    wins = [w for r in rep_k["sweeps"]["2"]["rungs"]
            for w in r["failover_windows"]]
    assert any(w["t_down"] == kill_s and w["t_recovered"] is not None
               for w in wins), f"failover window unnamed: {wins}"
    evs = capacity.report_events(rep_k)
    assert any(e["event"] == "capacity_frontier" and e["chaos"] == kill
               for e in evs)


def test_skyline_gauges_registered():
    spec = traffic.parse_spec("steady@rps=2:duration_s=2")
    capacity.plan_capacity(
        spec, replica_counts=(1,), rates=(1.0,),
        make_run_rung=lambda n: capacity.simulated_run_rung(n), seed=0)
    names = {m.name for m in obs.get_registry().instruments()}
    assert {"skyline_offered_rps", "skyline_goodput_tps",
            "skyline_slo_attainment",
            "skyline_sustainable_rps"} <= names


def test_knee_detection():
    # synthetic rungs: linear goodput then a hard saturation plateau
    def rung(x, y):
        return {"offered_rps": x, "goodput_tps": y,
                "slo": {}, "failover_windows": []}
    rungs = [rung(1, 10), rung(2, 20), rung(4, 40),
             rung(8, 44), rung(16, 45)]
    knee = capacity.knee_of(rungs)
    assert knee == 8  # first rate where marginal goodput collapses
    assert capacity.knee_of(rungs[:2]) is None  # too few points


# ---------------------------------------------------------------------------
# Satellite: Histogram.quantile
# ---------------------------------------------------------------------------


def _hist(buckets=(0.1, 1.0, 5.0)):
    return Histogram("q_test", "quantile edge cases", buckets=buckets)


def test_quantile_empty_is_zero():
    assert _hist().quantile(0.5) == 0.0


def test_quantile_single_observation_interpolates():
    h = _hist()
    h.observe(0.4)  # lands in the (0.1, 1.0] bucket
    assert h.quantile(0.0) == pytest.approx(0.1)
    assert h.quantile(0.5) == pytest.approx(0.55)
    assert h.quantile(1.0) == pytest.approx(1.0)


def test_quantile_all_overflow_clamps_to_last_bound():
    h = _hist()
    for _ in range(9):
        h.observe(50.0)  # beyond every finite bucket
    assert h.quantile(0.5) == 5.0
    assert h.quantile(0.99) == 5.0


def test_quantile_graded_distribution():
    h = _hist(buckets=(1.0, 2.0, 3.0, 4.0))
    for v in (0.5, 1.5, 2.5, 3.5):
        h.observe(v)
    assert h.quantile(0.5) == pytest.approx(2.0)
    assert h.quantile(1.0) == pytest.approx(4.0)


def test_quantile_validates_q_and_labels():
    h = Histogram("q_lbl", "labelled", buckets=(1.0,),
                  labels=("shape",))
    h.observe(0.5, shape="steady")
    with pytest.raises(ValueError):
        h.quantile(1.5, shape="steady")
    assert h.quantile(0.5, shape="steady") > 0.0
    assert h.quantile(0.5, shape="missing") == 0.0


# ---------------------------------------------------------------------------
# Satellite: seeded open-loop arrival schedule
# ---------------------------------------------------------------------------


def test_arrival_offsets_fixed_is_metronome():
    assert arrival_offsets(4, 10.0) == [0.0, 0.1, 0.2, 0.3]


def test_arrival_offsets_poisson_deterministic_per_seed():
    a = arrival_offsets(64, 25.0, arrival="poisson", seed=11)
    b = arrival_offsets(64, 25.0, arrival="poisson", seed=11)
    assert a == b  # the determinism regression: same seed, same schedule
    assert a[0] == 0.0 and a == sorted(a)
    c = arrival_offsets(64, 25.0, arrival="poisson", seed=12)
    assert c != a
    # mean gap tracks 1/rate (law of large numbers, loose bound)
    gaps = [y - x for x, y in zip(a, a[1:])]
    assert 0.5 / 25.0 < sum(gaps) / len(gaps) < 2.0 / 25.0


def test_arrival_offsets_rejects_bad_args():
    with pytest.raises(ValueError):
        arrival_offsets(4, 0.0)
    with pytest.raises(ValueError):
        arrival_offsets(4, 1.0, arrival="bursty")


# ---------------------------------------------------------------------------
# Satellite: obs.stats on hostile inputs
# ---------------------------------------------------------------------------


def test_percentile_heavy_tail_median_is_robust():
    rng = random.Random(5)
    # zipf-like: mostly small, a few enormous
    xs = [1.0 / (rng.random() ** 2 + 1e-4) for _ in range(500)]
    med = stats.median(xs)
    mean = sum(xs) / len(xs)
    assert med < mean  # the tail drags the mean, not the median
    assert stats.percentile(xs, 0.0) == min(xs)
    assert stats.percentile(xs, 1.0) == max(xs)
    assert stats.percentile(xs, 0.5) <= stats.percentile(xs, 0.99)
    assert stats.mad(xs) > 0.0


def test_percentile_nan_contamination_dropped():
    nan = float("nan")
    clean = [1.0, 2.0, 3.0, 4.0, 5.0]
    dirty = [nan, 1.0, 2.0, nan, 3.0, 4.0, 5.0, nan]
    for q in (0.0, 0.25, 0.5, 0.9, 1.0):
        got = stats.percentile(dirty, q)
        assert got == stats.percentile(clean, q)
        assert not math.isnan(got)
    assert stats.median(dirty) == 3.0
    assert not math.isnan(stats.mad(dirty))
    assert stats.percentile([nan, nan], 0.5) == 0.0  # all-NaN → empty


def test_mad_of_constant_is_zero():
    assert stats.mad([4.0] * 8) == 0.0
    assert stats.mad([]) == 0.0
