"""A whole closed-loop run of a tiny SDAR stage on the CPU.

Beside ``test_harness_kexaone.py``, for the block-diffusion decoder: the
run comes out ``correct``, its int8 control does not, and neither does
a run whose blocks are causal inside, one whose owed write is left out
(a finished block keeps its last denoising step's keys and values), nor
one whose experts' part is left out. ``serving.program_model`` passes a model
eight sizes and no more, so the sizes it does not pass (head size,
experts, the generation's) are the defaults of a tiny model registered
for the length of a test.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as bench_run
from benchmark.tests import tamper_sdar
from pytorch_distributed_nn_tpu import models, obs
from pytorch_distributed_nn_tpu.models.sdar_moe import SdarMoe
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _tiny_model_registered(monkeypatch):
    """In the registry for one test and out again: another file's test
    of what ``available_models()`` lists may share this session."""
    monkeypatch.setitem(models._REGISTRY, "sdar_tiny_for_tests", _tiny)
    # the readers sum the process's counters: a run of the benchmark is
    # a process of its own, a test is not
    obs.reset_registry()
    yield
    obs.reset_registry()


def _tiny(cfg):
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return SdarMoe(
        vocab_size=e["vocab_size"], num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        num_kv_heads=e["num_kv_heads"], rope_theta=e["rope_theta"],
        norm_eps=e["norm_eps"], head_dim=16, expert_mlp_dim=32,
        num_experts=8, moe_topk=2, block_length=4, denoising_steps=2,
        remasking="sequential", mask_token_id=511,
        dtype=policy.compute_dtype, param_dtype=policy.param_dtype)


def _serve(seconds: float, **kw):
    return bench_run.run_cell(
        workload="tiny_sdar", config_file=DATA / "tiny_sdar.json",
        traffic_file=DATA / "tiny_gen.json",
        cell_file=DATA / "cells" / "tiny_sdar.json", chips=1,
        seed=2**31 + 42, seconds=seconds, traced=False, check_device=False,
        **kw)


def test_closed_loop_cell_is_correct_and_its_control_is_not():
    run = _serve(2.0, control=True)
    assert run["correct"], run["check"]
    assert not run["control"]["correct"], run["control"]
    line = bench_run.result_line(
        run, [dict(name=n, unit="x") for n in (
            "serve_throughput", "setup_s", "tokens_per_forward.sdar",
            "cache_rows_attended_share", "held_experts_touched_share",
            "held_expert_pairs_per_round", "decode_round_p50",
            "prefill_share", "peak_hbm_share")], traced=False)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] and line["failed"] == 0
    assert m["serve_throughput"] > 0
    # a block of four in two steps, handed out by the second (no
    # forward only commits): 4 / 2, a little under it where a prompt's
    # tail or an answer's end cuts a block short
    assert 1.5 < m["tokens_per_forward.sdar"] <= 4 / 2 + 1e-9
    assert 0 < m["cache_rows_attended_share"] < 100
    assert 0 < m["held_experts_touched_share"] <= 100
    # every expert is held: live rows x the positions a row feeds (its
    # open block of 4, and the 4 of the block it owes) x 2 picks
    assert 0 < m["held_expert_pairs_per_round"] <= 4 * (4 + 4) * 2
    assert 0 < m["prefill_share"] < 100
    # traced-only readers say nothing in an untraced run
    assert bench_run.read_metrics(
        [dict(name=n, unit="%") for n in (
            "decode_hbm_share", "grouped_experts_hbm_share",
            "prefill_flops_share")], run) == {}


@pytest.mark.parametrize("fault", ["causal_block", "commit_skipped",
                                   "experts_zeroed"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    def tamper(engine):
        # an equal model's programs may be in jit's cache from a sound
        # run: they are traced anew with the fault, and again without
        jax.clear_caches()
        tamper_sdar.apply(fault, engine, monkeypatch.setattr)
    try:
        run = _serve(2.0, tamper=tamper)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not run["correct"], run["check"]
    assert run["check"][0]["name"] == "unfinished" \
        and run["check"][0]["value"] == 0
