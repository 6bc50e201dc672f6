"""A whole closed-loop run of a tiny LFM2 stage on the CPU.

Beside ``test_harness_jamba.py``, for the second served family whose
cache holds state: the run comes out ``correct``, its int8 control does
not, and neither does a run with one of ``tamper_lfm2.py``'s faults: the
carried inputs one position off at the hand-over, a prefill's padding
let into the tail, every expert's down projection zeroed, the attention
layers' cached keys zeroed. ``serving.program_model`` passes a model
eight sizes and no more, so the sizes it does not pass (the order of
operators, the experts) are the defaults of a tiny model registered for
the length of a test.
"""

from pathlib import Path

import jax
import pytest

from benchmark import run as bench_run
from benchmark.tests import tamper_lfm2
from pytorch_distributed_nn_tpu import models, obs
from pytorch_distributed_nn_tpu.models.lfm2_moe import Lfm2Moe
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _tiny_model_registered(monkeypatch):
    """In the registry for one test and out again: another file's test
    of what ``available_models()`` lists may share this session."""
    monkeypatch.setitem(models._REGISTRY, "lfm2_tiny_for_tests", _tiny)
    # the readers sum the process's counters: a run of the benchmark is
    # a process of its own, a test is not
    obs.reset_registry()
    yield
    obs.reset_registry()


def _tiny(cfg):
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return Lfm2Moe(
        vocab_size=e["vocab_size"], num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        num_kv_heads=e["num_kv_heads"], mlp_dim=e["mlp_dim"],
        rope_theta=e["rope_theta"], norm_eps=e["norm_eps"],
        expert_mlp_dim=32, num_experts=8, moe_topk=2, num_dense_layers=2,
        layer_types=("conv", "conv", "full_attention", "conv", "conv",
                     "full_attention", "conv"),
        dtype=policy.compute_dtype, param_dtype=policy.param_dtype)


def _serve(seconds: float, **kw):
    return bench_run.run_cell(
        workload="tiny_lfm2", config_file=DATA / "tiny_lfm2.json",
        traffic_file=DATA / "tiny_turns.json",
        cell_file=DATA / "cells" / "tiny_lfm2.json", chips=1,
        seed=2**31 + 46, seconds=seconds, traced=False, check_device=False,
        **kw)


def test_closed_loop_cell_is_correct_and_its_control_is_not():
    run = _serve(2.0, control=True)
    assert run["correct"], run["check"]
    assert not run["control"]["correct"], run["control"]
    line = bench_run.result_line(
        run, [dict(name=n, unit="x") for n in (
            "serve_throughput", "setup_s", "state_bytes_share",
            "cache_rows_attended_share",
            "held_experts_touched_share", "held_expert_pairs_per_round",
            "conv_rows_per_round.lfm2", "decode_round_p50",
            "prefill_share")], traced=False)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] and line["failed"] == 0
    assert m["serve_throughput"] > 0
    # four rows' two carried inputs in five layers beside ~150 K
    # parameters
    assert 0 < m["state_bytes_share"] < 10
    assert 0 < m["cache_rows_attended_share"] < 100
    assert 0 < m["held_experts_touched_share"] <= 100
    # four slots; every expert is held, so active rows x the picks
    assert 0 < m["conv_rows_per_round.lfm2"] <= 4
    assert m["held_expert_pairs_per_round"] \
        == pytest.approx(2 * m["conv_rows_per_round.lfm2"])
    assert 0 < m["prefill_share"] < 100
    # traced-only readers say nothing in an untraced run
    assert bench_run.read_metrics(
        [dict(name=n, unit="%") for n in (
            "decode_hbm_share", "grouped_experts_hbm_share",
            "prefill_flops_share", "prefill_pad_share")],
        run) == {}


@pytest.mark.parametrize("fault", tamper_lfm2.FAULTS)
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    def tamper(engine):
        # an equal model's programs may be in jit's cache from a sound
        # run: they are traced anew with the fault, and again without
        jax.clear_caches()
        tamper_lfm2.apply(fault, engine, monkeypatch.setattr)
    try:
        run = _serve(2.0, tamper=tamper)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not run["correct"], run["check"]
    assert run["check"][0]["name"] == "unfinished" \
        and run["check"][0]["value"] == 0
