"""The schedule is pinned by the traffic file; ``--seed`` only makes
token ids."""

from pathlib import Path

import numpy as np
import pytest

from benchmark.lib import common, traffic

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("name", ["chat_replay", "docs_closed8"])
def test_two_seeds_send_the_same_schedule_and_other_tokens(name):
    spec = common.load_json(TRAFFIC / f"{name}.json")
    a = traffic.schedule_jsonl(spec, 30.0)
    b = traffic.schedule_jsonl(common.load_json(TRAFFIC / f"{name}.json"),
                               30.0)
    assert a == b and len(a) > 0         # byte-identical, run after run
    sched = traffic.schedule(spec, 30.0)
    flat = sched if spec["kind"] == "serve_open" else \
        [r for mine in sched for r in mine]
    # the schedule takes no seed at all; the token ids take nothing else
    for rec in flat[:8]:
        p1 = traffic.prompt_tokens(rec, 11, 32768)
        p2 = traffic.prompt_tokens(rec, 2**31 + 12, 32768)
        assert len(p1) == len(p2) == rec["prompt_len"]
        assert not np.array_equal(p1, p2)
        assert np.array_equal(p1, traffic.prompt_tokens(rec, 11, 32768))
    lo, hi = spec["prompt"]["min"], spec["prompt"]["max"]
    assert all(lo <= r["prompt_len"] <= hi for r in flat)
    assert all(spec["output"]["min"] <= r["max_new"] <= spec["output"]["max"]
               for r in flat)


def test_another_schedule_seed_is_another_trace():
    spec = common.load_json(TRAFFIC / "chat_replay.json")
    other = dict(spec, schedule_seed=spec["schedule_seed"] + 1)
    assert traffic.schedule_jsonl(spec, 30.0) != \
        traffic.schedule_jsonl(other, 30.0)


def test_open_loop_rate_and_order():
    spec = common.load_json(TRAFFIC / "chat_replay.json")
    sched = traffic.schedule(spec, 40.0)
    ts = [r["t"] for r in sched]
    assert ts == sorted(ts) and ts[-1] < 40.0
    want = spec["arrivals"]["rate_rps"] * 40.0
    assert abs(len(sched) - want) < 4 * want ** 0.5
    assert [r["i"] for r in sched] == list(range(len(sched)))


def test_flash_crowds_keep_the_mean_rate():
    spec = common.load_json(TRAFFIC / "chat_replay.json")
    spec["arrivals"]["flash"] = [
        {"every_s": 15, "peak": 4, "ramp_s": 1, "hold_s": 2}]
    n = len(traffic.schedule(spec, 45.0))
    want = spec["arrivals"]["rate_rps"] * 45.0
    assert abs(n - want) < 4 * want ** 0.5


def test_shared_prefixes():
    spec = common.load_json(TRAFFIC / "chat_replay.json")
    spec["shared_prefix"] = {"groups": 2, "length": 64, "share": 1.0}
    sched = traffic.schedule(spec, 10.0)
    by_group = {}
    for r in sched:
        p = traffic.prompt_tokens(r, 5, 32768)
        assert len(p) == r["prompt_len"] > 64
        by_group.setdefault(r["prefix_group"], []).append(p[:64])
    for heads in by_group.values():
        assert all(np.array_equal(heads[0], h) for h in heads)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 201))
    assert common.percentile(xs, 90) == 180
    assert common.percentile(xs, 95) == 190
    assert common.percentile([5.0], 99) == 5.0
