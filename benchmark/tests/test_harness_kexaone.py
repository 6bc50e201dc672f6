"""A whole closed-loop run of a tiny K-EXAONE rank on the CPU.

Beside ``test_harness_longcat.py``, for the third served family: the
run comes out ``correct``, its int8 control does not, and neither does
a run whose held experts' part is left out nor one whose ring rows are
written one row off. ``serving.program_model`` passes a model eight
sizes and no more, so the sizes it does not pass (head size, window,
experts) are the defaults of a tiny model registered for the length of
a test.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as bench_run
from pytorch_distributed_nn_tpu import models, obs
from pytorch_distributed_nn_tpu.models.k_exaone import KExaone
from pytorch_distributed_nn_tpu.nn import attention
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy

DATA = Path(__file__).parent / "data"
WINDOW = 8


@pytest.fixture(autouse=True)
def _tiny_model_registered(monkeypatch):
    """In the registry for one test and out again: another file's test
    of what ``available_models()`` lists may share this session."""
    monkeypatch.setitem(models._REGISTRY, "k_exaone_tiny_for_tests", _tiny)
    # the readers sum the process's counters: a run of the benchmark is
    # a process of its own, a test is not
    obs.reset_registry()
    yield
    obs.reset_registry()


def _tiny(cfg):
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return KExaone(
        vocab_size=e["vocab_size"], num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        num_kv_heads=e["num_kv_heads"], mlp_dim=e["mlp_dim"],
        rope_theta=e["rope_theta"], norm_eps=e["norm_eps"], head_dim=16,
        window=WINDOW, expert_mlp_dim=32, num_experts=16, moe_topk=4,
        ep_size=2, ep_rank=0, dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype)


def _serve(seconds: float, **kw):
    return bench_run.run_cell(
        workload="tiny_kexaone", config_file=DATA / "tiny_kexaone.json",
        traffic_file=DATA / "tiny_reason.json",
        cell_file=DATA / "cells" / "tiny_kexaone.json", chips=1,
        seed=2**31 + 33, seconds=seconds, traced=False, check_device=False,
        **kw)


def test_closed_loop_cell_is_correct_and_its_control_is_not():
    run = _serve(2.0, control=True)
    assert run["correct"], run["check"]
    assert not run["control"]["correct"], run["control"]
    line = bench_run.result_line(
        run, [dict(name=n, unit="x") for n in (
            "serve_throughput", "setup_s",
            "held_expert_pairs_per_round",
            "held_experts_touched_share",
            "full_rows_attended_share.kexaone", "ring_read_share.kexaone",
            "decode_round_p50", "prefill_share",
            "peak_hbm_share")], traced=False)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] and line["failed"] == 0
    assert m["serve_throughput"] > 0
    # 8 of 16 router outputs are held here, 4 picks a token
    assert 0 < m["held_experts_touched_share"] <= 100
    assert 0 < m["held_expert_pairs_per_round"] <= 4 * 4
    # three sparse ring layers of 8 rows, one full layer of 128 (the
    # dense layer's ring too: four rings in all)
    assert m["ring_read_share.kexaone"] == pytest.approx(
        100 * 4 * 8 / (4 * 8 + 128))
    assert 0 < m["full_rows_attended_share.kexaone"] < 100
    # a traced-only reader says nothing in an untraced run
    assert bench_run.read_metrics(
        [dict(name="decode_hbm_share", unit="%")], run) == {}


def test_held_experts_left_out_is_not_correct():
    def tamper(engine):
        def zero_down(path, leaf):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            return jnp.zeros_like(leaf) \
                if name.endswith("moe/experts_down") else leaf
        engine.params = jax.tree_util.tree_map_with_path(
            zero_down, engine.params)
    run = _serve(2.0, tamper=tamper)
    assert not run["correct"], run["check"]


def test_ring_rows_written_one_row_off_is_not_correct(monkeypatch):
    """A decode round that writes position p to row (p + 1) mod window:
    the row the mask reads as p holds a position one window older."""
    write = attention._row_update

    def one_off(buf, new, starts):
        if buf.shape[1] == WINDOW:
            starts = (starts + 1) % WINDOW
        return write(buf, new, starts)

    def tamper(engine):
        del engine
        # an equal model's programs may be in jit's cache from a sound
        # run: they are traced anew with the fault, and again without
        jax.clear_caches()
        monkeypatch.setattr(attention, "_row_update", one_off)
    try:
        run = _serve(2.0, tamper=tamper)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not run["correct"], run["check"]
