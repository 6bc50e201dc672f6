"""Charging idle time to the serve loop's spans: on hand-made intervals
and on a trace recorded on the chip.

``data/recorded_spans.xplane.pb`` was taken on a TPU v5e by
``record_spans.py``: a two-layer decoder behind the program's server,
two requests together, a pause, then the longer prompt again (its first
block restored from the prefix cache).
"""

from pathlib import Path

import pytest

from benchmark.lib import common
from benchmark.lib import host_spans as hs
from benchmark.lib import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "recorded_spans.xplane.pb"


def _sp(name, s, e, **stats):
    return (name, float(s), float(e), stats)


def test_intersect():
    assert hs.intersect([(0, 10), (20, 30)], [(5, 25)]) == \
        [(5, 10), (20, 25)]
    assert hs.intersect([(0, 10)], []) == []
    assert hs.intersect([], [(0, 10)]) == []


# one round with an admission pass, one plain round, a wait:
#   round 0-100: admit 10-50 (retire 44-48 inside it), decode 52-80,
#     round_host 80-98 with retire 90-96 inside it
#   round 110-150: decode 112-140, round_host 140-149
#   parked 160-200
SPANS = [
    _sp("serve/round", 0, 100, round=1, occ=2),
    _sp("serve/admit", 10, 50, n=1),
    _sp("serve/prefill_into", 12, 44, request="req-1", tokens=5,
        padded=16, cached=0, row_len=16),
    _sp("serve/fresh_cache", 13, 20),
    _sp("serve/prefill", 22, 38, request="req-1"),
    _sp("serve/insert_row", 39, 43, rows=1),
    _sp("serve/retire", 44, 48, n=0),
    _sp("serve/decode", 52, 80),
    _sp("serve/round_host", 80, 98, retired=1),
    _sp("serve/retire", 90, 96, n=1),
    _sp("serve/round", 110, 150, round=2, occ=1),
    _sp("serve/decode", 112, 140),
    _sp("serve/round_host", 140, 149, retired=0),
    _sp("serve/parked", 160, 200),
]


def test_class_cover_takes_each_instant_once():
    cover = hs.class_cover(SPANS)
    assert cover["admit"] == [(10, 50)]
    assert cover["retire"] == [(90, 96)]      # 44-48 is the admission's
    assert cover["round_return"] == [(52, 90), (96, 98), (112, 149)]
    assert cover["parked"] == [(160, 200)]
    everything = sorted(iv for c in cover.values() for iv in c)
    assert tr.total(tr.union(everything)) == tr.total(everything)


def test_charge_on_a_hand_made_list():
    idle = [(0, 30),     # 10 under nothing, then 20 of the admission
            (46, 54),    # 4 under admit (its retire), 2 bare, 2 decode
            (88, 100),   # 2 round_host, 6 retire, 2 round_host, 2 bare
            (100, 110),  # between rounds: under nothing
            (150, 170)]  # 10 bare, 10 parked
    got = hs.charge(idle, SPANS)
    assert got == dict(admit=24.0, retire=6.0, round_return=6.0,
                       parked=10.0, unattributed=34.0)
    assert sum(got.values()) == tr.total(idle)


def test_charge_without_spans_leaves_everything_unattributed():
    got = hs.charge([(0, 10)], [])
    assert got["unattributed"] == 10.0
    assert sum(got.values()) == 10.0


def test_unattributed_parts():
    idle = [(-10, 5),     # 10 before the first span, 5 in round 1
            (8, 12),      # 2 ahead of the admission, 2 inside it
            (100, 110),   # between the rounds
            (149, 155),   # 1 in round 2 after its last child, 5 between
            (195, 230)]   # 5 parked, 30 after the last span
    parts = hs.unattributed_parts(idle, SPANS)
    assert parts == dict(off_host=40.0, before_admit=7.0, in_round=1.0,
                         between_rounds=15.0)
    assert sum(parts.values()) == hs.charge(idle, SPANS)["unattributed"]


def test_second_level_and_clock_check():
    idle = [(0, 30), (46, 54)]
    sec = hs.second_level(idle, SPANS, w0=-1e9)
    n, span_s, idle_s, longest_ms, at_s = sec["serve/fresh_cache"]
    assert (n, span_s, idle_s) == (1, 7e-9, 7e-9)
    assert sec["serve/retire"][0] == 2
    assert sec["serve/retire"][3] == 6e-6 and sec["serve/retire"][4] == \
        pytest.approx(1.0 + 90e-9)
    devs = {0: dict(modules=[("jit__serve_step", 55, 78),
                             ("jit__serve_step", 111, 139),
                             ("jit__serve_prefill", 25, 35),
                             ("jit__serve_step", 300, 320)],
                    ops=[], **{"async": []})}
    c = hs.clock_check(SPANS, devs)
    assert c["executions"] == 2     # the last one has no span
    assert c["nested"] == 1
    assert c["head_slack_min_us"] == -1e-3   # 111 before 112


def _run():
    return dict(trace=dict(window_s=1.0), workload="no_such_cell")


def test_readers_say_nothing_without_a_trace_or_without_spans(tmp_path):
    assert hs.idle_share_pct(dict(trace=None, workload="x"), "admit") \
        is None
    assert hs.idle_share_pct(_run(), "admit") is None   # no trace file
    assert hs.span_p50_ms(_run(), "serve/admit") is None
    assert hs.prefill_pad_share_pct(_run()) is None
    # a trace of a program that has no spans (the parent commit)
    plain = Path(__file__).parent / "data" / "recorded.xplane.pb"
    assert hs.load_spans(str(plain)) == []
    assert hs.analyze(str(plain)) is None


# -- the trace recorded on the chip -----------------------------------------

SPAN_NAMES = ("serve/round", "serve/admit", "serve/prefill_into",
              "serve/fresh_cache", "serve/restore", "serve/prefill",
              "serve/insert_row", "serve/decode", "serve/round_host",
              "serve/retire", "serve/parked")


@pytest.fixture(scope="module")
def recorded():
    return hs.analyze(str(RECORDED))


def test_recorded_file_holds_every_span_on_one_line(recorded):
    spans = recorded["spans"]
    assert {n for n, *_ in spans} == set(SPAN_NAMES)
    count = {k: sum(1 for n, *_ in spans if n == k) for k in SPAN_NAMES}
    assert count["serve/round"] == 5 and count["serve/admit"] == 2
    assert count["serve/prefill_into"] == count["serve/prefill"] == 3
    assert count["serve/restore"] == 2 and count["serve/parked"] == 10
    # nesting: every admit, decode and round_host lies inside a round
    rounds = [(s, e) for n, s, e, _ in spans if n == "serve/round"]
    for n, s, e, _ in spans:
        if n in ("serve/admit", "serve/decode", "serve/round_host"):
            assert any(rs <= s and e <= re for rs, re in rounds), n
    into = [st for n, _, _, st in spans if n == "serve/prefill_into"]
    assert [(st["tokens"], st["padded"], st["cached"]) for st in into] \
        == [(5, 16, 0), (3, 16, 16), (3, 16, 16)]
    assert [st["request"] for st in into] == ["req-3", "req-4", "req-5"]


def test_recorded_classes_sum_to_the_idle_share(recorded):
    devs = tr.load(str(RECORDED))
    summary = tr.summarize(devs, recorded["window_ns"] / 1e9)
    idle_pct = 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
    by_class = recorded["by_class"]
    assert set(by_class) == {"admit", "retire", "round_return", "parked",
                             "unattributed"}
    assert all(v >= 0.0 for v in by_class.values())
    assert sum(by_class.values()) == pytest.approx(recorded["idle_ns"])
    assert 100.0 * recorded["idle_ns"] / recorded["window_ns"] == \
        pytest.approx(idle_pct, abs=1e-6)
    # a decoder this small leaves the chip idle nearly always; most of
    # it under the two admission passes, none of it unexplained
    assert idle_pct == pytest.approx(99.30, abs=0.01)
    share = {k: 100.0 * v / recorded["window_ns"]
             for k, v in by_class.items()}
    assert share["admit"] == pytest.approx(55.53, abs=0.01)
    assert share["round_return"] == pytest.approx(24.70, abs=0.01)
    assert share["retire"] == pytest.approx(2.43, abs=0.01)
    assert share["parked"] == pytest.approx(15.49, abs=0.01)
    assert share["unattributed"] == pytest.approx(1.15, abs=0.01)
    assert sum(recorded["unattributed"].values()) == \
        pytest.approx(by_class["unattributed"])


def test_recorded_clock_check(recorded):
    """The device's clock runs early against the host's in this file:
    two of the four decode executions begin before the span that
    dispatched them does."""
    c = recorded["clock"]
    assert c["executions"] == 4 and c["nested"] == 2
    assert c["head_slack_min_us"] == pytest.approx(-651.9, abs=0.1)
    assert c["tail_slack_p50_us"] == pytest.approx(1216.5, abs=0.1)


METRICS = {
    "idle_admit_share.chat": 55.53, "idle_admit_share": 55.53,
    "idle_round_return_share.chat": 24.70,
    "idle_round_return_share": 24.70,
    "idle_retire_share.chat": 2.43, "idle_parked_share.chat": 15.49,
    "idle_unattributed_share.chat": 1.15,
    "idle_unattributed_share": 1.15 + 2.43 + 15.49,
    "admit_stall_p50.chat": 14.213, "round_host_p50.chat": 3.305,
    "prefill_pad_share": 100.0 * (1.0 - 11.0 / 48.0),
}


@pytest.fixture()
def traced_run(tmp_path, monkeypatch):
    """A run whose trace directory holds the recorded file."""
    d = tmp_path / ".bench_trace" / "some_cell" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(RECORDED.read_bytes())
    monkeypatch.setattr(hs, "ROOT", tmp_path)
    return dict(trace=dict(window_s=1.0), workload="some_cell")


def _reader(name):
    return common.load_module(
        Path(__file__).parents[1] / "metrics" / f"{name}.py",
        "test_metric_" + name.replace(".", "_")).read


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reader_on_the_recorded_file(traced_run, name):
    bench = common.load_json(Path(__file__).parents[2] / "BENCHMARK.json")
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span"
    assert entry["layer"] == "Serve loop" and entry["workloads"]
    read = _reader(name)
    assert read(traced_run) == pytest.approx(METRICS[name], abs=0.01)
    assert read(dict(trace=None, workload="some_cell")) is None


def test_cell_classes_sum_to_the_cells_idle_share(traced_run):
    def read(name):
        return _reader(name)(traced_run)

    chat = sum(read(f"idle_{k}_share.chat") for k in
               ("admit", "round_return", "retire", "parked", "unattributed"))
    docs = sum(read(f"idle_{k}_share") for k in
               ("admit", "round_return", "unattributed"))
    assert chat == pytest.approx(99.30, abs=0.01)
    assert docs == pytest.approx(chat)
