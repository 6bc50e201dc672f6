"""A whole closed-loop run of a tiny A.X-K1 rank on the CPU.

Beside ``test_harness_kexaone.py``, for the fourth served family and the
first cell whose traffic hits the prefix store: three requests in four
begin with one of two shared documents of 48 tokens (three blocks of
16), which the engine restores from its block store. The run comes out
``correct`` with hits among the requests it compares, its int8 control
does not, and neither does a run whose held experts' part is left out,
one whose restore copies one block too few, nor one whose restored rows
lie one row off. ``serving.program_model`` passes a model eight sizes
and no more, so the sizes it does not pass (latent ranks, head sizes,
experts, groups, the rotation's original positions) are the defaults of
a tiny model registered for the length of a test.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as bench_run
from pytorch_distributed_nn_tpu import models, obs
from pytorch_distributed_nn_tpu.models.ax_k1 import AXK1
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.serve import engine as engine_mod

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _tiny_model_registered(monkeypatch):
    """In the registry for one test and out again: another file's test
    of what ``available_models()`` lists may share this session."""
    monkeypatch.setitem(models._REGISTRY, "ax_k1_tiny_for_tests", _tiny)
    # the readers sum the process's counters: a run of the benchmark is
    # a process of its own, a test is not
    obs.reset_registry()
    yield
    obs.reset_registry()


def _tiny(cfg):
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return AXK1(
        vocab_size=e["vocab_size"], num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        mlp_dim=e["mlp_dim"], rope_theta=e["rope_theta"],
        norm_eps=e["norm_eps"], q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        expert_mlp_dim=32, num_experts=16, moe_topk=4, n_group=4,
        topk_group=2, rope_original_positions=32, ep_size=2, ep_rank=0,
        dtype=policy.compute_dtype, param_dtype=policy.param_dtype)


def _serve(seconds: float, **kw):
    return bench_run.run_cell(
        workload="tiny_axk1", config_file=DATA / "tiny_axk1.json",
        traffic_file=DATA / "tiny_docqa.json",
        cell_file=DATA / "cells" / "tiny_axk1.json", chips=1,
        seed=2**31 + 35, seconds=seconds, traced=False, check_device=False,
        **kw)


def test_closed_loop_cell_is_correct_and_its_control_is_not():
    run = _serve(2.0, control=True)
    assert run["correct"], run["check"]
    assert not run["control"]["correct"], run["control"]
    line = bench_run.result_line(
        run, [dict(name=n, unit="x") for n in (
            "serve_throughput", "setup_s",
            "held_expert_pairs_per_round",
            "held_experts_touched_share", "pick_groups_per_token.axk1",
            "latent_rows_attended_share.axk1", "decode_round_p50",
            "prefill_share", "peak_hbm_share",
            "prefix_cached_token_share.axk1")], traced=False)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] and line["failed"] == 0
    assert m["serve_throughput"] > 0
    # 8 of 16 router outputs are held here, 4 picks a token from 2 groups
    assert 0 < m["held_experts_touched_share"] <= 100
    assert 0 < m["held_expert_pairs_per_round"] <= 4 * 4
    assert 1.0 <= m["pick_groups_per_token.axk1"] <= 2.0
    assert 0 < m["latent_rows_attended_share.axk1"] < 100
    # documents of 48 tokens in prompts of 52 to 64, three in four
    assert 20 < m["prefix_cached_token_share.axk1"] < 100 * 48 / 52
    # the traced-only readers say nothing in an untraced run
    assert bench_run.read_metrics(
        [dict(name=n, unit="%") for n in (
            "decode_hbm_share", "prefill_flops_share",
            "restore_share.axk1")],
        run) == {}


def test_the_engine_restored_the_shared_documents():
    """What the cell exists for, read off the engine itself: of the
    requests admitted, those that begin with a document some retired
    request left in the store were served 48 tokens from it."""
    seen = {}

    def tamper(engine):
        seen["completed"] = engine.completed
    run = _serve(2.0, tamper=tamper)
    assert run["correct"], run["check"]
    done = seen["completed"]
    cached = [c["cached_tokens"] for c in done]
    # whole blocks of a document; at this small a pool (32 blocks for
    # four rows of 8) a chain's last block is sometimes shed and comes
    # back with the next retire
    assert set(cached) <= {0, 16, 32, 48}
    assert cached.count(48) > len(cached) // 2


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


@pytest.mark.parametrize("fault", ["one_block_short", "one_row_off"])
def test_a_faulty_restore_is_not_correct(monkeypatch, fault):
    """A restore that copies one block too few (the last 16 rows of the
    document stay zero), or whose rows land one row off."""
    restore = engine_mod._restore_blocks

    def faulty(row_cache, store, block_size, table, n):
        if fault == "one_block_short":
            return restore(row_cache, store, block_size, table, n - 1)
        out = restore(row_cache, store, block_size, table, n)
        return jax.tree.map(
            lambda r: jnp.roll(r, 1, axis=1) if r.ndim >= 2 else r, out)

    def tamper(engine):
        del engine
        monkeypatch.setattr(engine_mod, "_restore_blocks", faulty)
    try:
        run = _serve(2.0, tamper=tamper)
    finally:
        monkeypatch.undo()
    assert not run["correct"], run["check"]
