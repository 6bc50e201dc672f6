"""A whole closed-loop run of a tiny Brumby on the CPU.

Beside ``test_harness_jamba.py``, for the eighth served family and the
first whose cache is all state, a matrix a head: the run comes out
``correct``, its int8 control does not, and neither does a run whose
state (or whose normaliser) is zeroed when a prefilled row joins the
batch, nor one whose insert skips the state (a slot keeps what its last
tenant left). ``serving.program_model`` passes a model eight sizes and
no more, so the head's size is the default of a tiny model registered
for the length of a test.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as bench_run
from pytorch_distributed_nn_tpu import models, obs
from pytorch_distributed_nn_tpu.models.brumby import Brumby
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.serve import engine as engine_mod

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _tiny_model_registered(monkeypatch):
    """In the registry for one test and out again: another file's test
    of what ``available_models()`` lists may share this session."""
    monkeypatch.setitem(models._REGISTRY, "brumby_tiny_for_tests", _tiny)
    # the readers sum the process's counters: a run of the benchmark is
    # a process of its own, a test is not
    obs.reset_registry()
    yield
    obs.reset_registry()


def _tiny(cfg):
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return Brumby(
        vocab_size=e["vocab_size"], num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        num_kv_heads=e["num_kv_heads"], mlp_dim=e["mlp_dim"],
        head_dim=e["d_model"] // e["num_heads"],
        rope_theta=e["rope_theta"], norm_eps=e["norm_eps"],
        dtype=policy.compute_dtype, param_dtype=policy.param_dtype)


def _serve(seconds: float, **kw):
    return bench_run.run_cell(
        workload="tiny_brumby", config_file=DATA / "tiny_brumby.json",
        traffic_file=DATA / "tiny_reader.json",
        cell_file=DATA / "cells" / "tiny_brumby.json", chips=1,
        seed=2**31 + 50, seconds=seconds, traced=False, check_device=False,
        **kw)


def test_closed_loop_cell_is_correct_and_its_control_is_not():
    run = _serve(2.0, control=True)
    assert run["correct"], run["check"]
    assert not run["control"]["correct"], run["control"]
    line = bench_run.result_line(
        run, [dict(name=n, unit="x") for n in (
            "serve_throughput", "setup_s", "state_bytes_share",
            "decode_round_p50", "prefill_share",
            "peak_hbm_share")], traced=False)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] and line["failed"] == 0
    assert m["serve_throughput"] > 0
    # four rows' state in three layers (2 x 192 x 17 float32 a layer a
    # row, in and out) beside ~140 K parameters in bf16
    assert 50 < m["state_bytes_share"] < 80
    # the traced-only readers say nothing in an untraced run
    assert bench_run.read_metrics(
        [dict(name=n, unit="%") for n in (
            "decode_hbm_share", "prefill_flops_share",
            "retention_step_hbm_share.brumby",
            "retention_chunk_flops_share.brumby",
            "prefill_pad_share")], run) == {}


def _without(leaf: str):
    """``_insert_row`` that leaves the named state leaf of the slot as
    its last tenant left it."""
    insert = engine_mod._insert_row

    def faulty(batch_cache, row_cache, slot, **kw):
        # (a copy: the insert donates the batch cache)
        kept = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.copy(x)
            if getattr(path[-1], "key", "") == leaf else None, batch_cache)
        out = insert(batch_cache, row_cache, slot, **kw)
        return jax.tree_util.tree_map_with_path(
            lambda path, new, old: old
            if getattr(path[-1], "key", "") == leaf else new, out, kept,
            is_leaf=lambda x: x is None)
    return faulty


def _zeroed(leaf: str):
    insert = engine_mod._insert_row

    def faulty(batch_cache, row_cache, slot, **kw):
        row_cache = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x)
            if getattr(path[-1], "key", "") == leaf else x, row_cache)
        return insert(batch_cache, row_cache, slot, **kw)
    return faulty


@pytest.mark.parametrize("fault", ["state_zeroed", "norm_zeroed",
                                   "state_not_inserted"])
def test_a_state_that_does_not_reach_its_slot_is_not_correct(
        monkeypatch, fault):
    """The state zeroed at the hand-over (a prefilled row joins the
    batch with nothing of its prompt); the normaliser zeroed there; the
    insert made to skip the state (a slot keeps what its last tenant
    left)."""
    def tamper(engine):
        del engine
        monkeypatch.setattr(engine_mod, "_insert_row", {
            "state_zeroed": _zeroed("ret_state"),
            "norm_zeroed": _zeroed("ret_norm"),
            "state_not_inserted": _without("ret_state")}[fault])
    try:
        run = _serve(2.0, tamper=tamper)
    finally:
        monkeypatch.undo()
    assert not run["correct"], run["check"]
