"""A whole closed-loop run of a tiny LongCat-Flash rank on the CPU.

Beside ``test_harness.py``'s three served-cell tests, for the second
served family: the run comes out ``correct``, its int8 control does
not, and neither does a run whose held experts' part is left out.
``serving.program_model`` passes a model eight sizes and no more, so
the sizes it does not pass (ranks, head sizes, experts) are the
defaults of a tiny model registered for the length of a test.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as bench_run
from pytorch_distributed_nn_tpu import models
from pytorch_distributed_nn_tpu.models.longcat_flash import LongcatFlash
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _tiny_model_registered(monkeypatch):
    """In the registry for one test and out again: another file's test
    of what ``available_models()`` lists may share this session."""
    monkeypatch.setitem(models._REGISTRY, "longcat_flash_tiny_for_tests",
                        _tiny)


def _tiny(cfg):
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return LongcatFlash(
        vocab_size=e["vocab_size"], num_layers=e["num_layers"],
        d_model=e["d_model"], num_heads=e["num_heads"],
        mlp_dim=e["mlp_dim"], rope_theta=e["rope_theta"],
        norm_eps=e["norm_eps"], q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        expert_mlp_dim=32, num_experts=16, num_zero_experts=8, moe_topk=4,
        routed_scaling=1.0, ep_size=2, ep_rank=0,
        dtype=policy.compute_dtype, param_dtype=policy.param_dtype)


def _serve(seconds: float, **kw):
    return bench_run.run_cell(
        workload="tiny_longcat", config_file=DATA / "tiny_longcat.json",
        traffic_file=DATA / "tiny_assist.json",
        cell_file=DATA / "cells" / "tiny_longcat.json", chips=1,
        seed=2**31 + 29, seconds=seconds, traced=False, check_device=False,
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
            "zero_expert_pick_share.longcat", "decode_round_p50",
            "prefill_share", "peak_hbm_share")], traced=False)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] and line["failed"] == 0
    assert m["serve_throughput"] > 0
    # 8 of 24 router outputs are zero experts, 8 are held here
    assert 15 < m["zero_expert_pick_share.longcat"] < 55
    assert 0 < m["held_experts_touched_share"] <= 100
    assert 0 < m["held_expert_pairs_per_round"] <= 4 * 4
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
