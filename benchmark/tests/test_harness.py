"""The rest of a run, driven without the look for a chip, at tiny sizes.

Rehearsal 1 of the on-chip-measurement guide, and the two tests the
contract asks to keep: the control (the reference in the precision
below the configuration's) comes out as not correct, and so does a run
whose timed path is broken underneath.
"""

from pathlib import Path

import numpy as np
import pytest

from benchmark import run as bench_run

DATA = Path(__file__).parent / "data"


def _serve(traffic: str, seconds: float, **kw):
    return bench_run.run_cell(
        workload=f"tiny_{traffic}", config_file=DATA / "tiny_decoder.json",
        traffic_file=DATA / f"tiny_{traffic}.json",
        cell_file=DATA / "cells" / "tiny_serve.json", chips=1, seed=2**31 + 7,
        seconds=seconds, traced=False, check_device=False, **kw)


def _train(seconds: float, **kw):
    return bench_run.run_cell(
        workload="tiny_train", config_file=DATA / "tiny_encoder.json",
        traffic_file=DATA / "tiny_mlm.json",
        cell_file=DATA / "cells" / "tiny_train.json", chips=1, seed=5,
        seconds=seconds, traced=False, check_device=False, **kw)


def _line(run, names):
    entries = [dict(name=n, unit="x") for n in names]
    return bench_run.result_line(run, entries, traced=False)


def test_open_loop_cell_is_correct_and_its_control_is_not():
    run = _serve("chat", 3.0, control=True)
    assert run["correct"], run["check"]
    assert not run["control"]["correct"], run["control"]
    line = _line(run, ["ttft_p90", "itl_p95", "setup_s"])
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == len(run["sent"]) > 5
    assert set(line["metrics"]) == {"ttft_p90", "itl_p95", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # timed from when each request was due, over every request due
    for s in run["sent"]:
        assert s.due == pytest.approx(run["t0"] + s.rec["t"])
        assert s.sent >= s.due and s.arrivals[0] >= s.sent
        assert len(s.tokens) == s.rec["max_new"]


def test_closed_loop_cell_counts_tokens_as_they_arrive():
    run = _serve("docs", 2.0)
    assert run["correct"], run["check"]
    line = _line(run, ["serve_throughput", "setup_s"])
    tput = line["metrics"]["serve_throughput"]["value"]
    inside = sum(1 for s in run["sent"] for a in s.arrivals
                 if run["t0"] <= a <= run["t1"])
    assert tput * (run["t1"] - run["t0"]) >= inside > 0
    # the window opened once every caller had finished a request
    assert {s.client for s in run["before_window"]} == {0, 1}


def test_a_token_altered_where_it_is_produced_is_not_correct():
    def tamper(engine):
        orig = engine._decode_round

        def altered():
            tok, dt = orig()
            return (np.asarray(tok) + 1) % 512, dt
        engine._decode_round = altered
    run = _serve("chat", 2.0, tamper=tamper)
    assert not run["correct"], run["check"]
    assert not _line(run, ["setup_s"])["correct"]


def test_train_cell_is_correct_and_its_control_is_not():
    run = _train(1.0, control=True)
    assert run["correct"], run["check"]
    assert not run["control"]["correct"], run["control"]
    line = _line(run, ["train_throughput", "setup_s"])
    assert line["metrics"]["train_throughput"]["value"] > 0
    assert line["attempted"] == run["steps"] > 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    import jax
    import jax.numpy as jnp

    def tamper(trainer):
        orig = trainer.step_fn

        def frozen(state, x, y):
            _, metrics = orig(jax.tree.map(jnp.copy, state), x, y)
            return state, metrics
        trainer.step_fn = frozen
    run = _train(0.0, tamper=tamper)
    assert not run["correct"], run["check"]
    bad = {r["name"] for r in run["check"] if not r["ok"]}
    assert "delta_norm_gap" in bad
