"""A.X-K1's readers that read the serve loop's spans, on the trace
recorded on the chip (``data/recorded_spans.xplane.pb``, see
``test_host_spans.py``): three admissions, a whole prompt of 5 tokens
padded to 16, then twice 3 tokens behind one restored block of 16."""

from pathlib import Path

import pytest

from benchmark.lib import common, costs_axk1
from benchmark.lib import host_spans as hs
from pytorch_distributed_nn_tpu import obs

RECORDED = Path(__file__).parent / "data" / "recorded_spans.xplane.pb"
CFG = dict(hidden_size=8, num_attention_heads=2, q_lora_rank=4,
           kv_lora_rank=3, qk_nope_head_dim=2, qk_rope_head_dim=2,
           v_head_dim=3, intermediate_size=16, moe_intermediate_size=4,
           n_routed_experts=2, num_experts_per_tok=2, n_shared_experts=1,
           num_hidden_layers=4, first_k_dense_replace=1, vocab_size=10,
           torch_dtype="bfloat16", expert_parallel=dict(ep_size=3, ep_rank=0),
           program=dict(readers="readers_axk1"))


@pytest.fixture()
def traced_run(tmp_path, monkeypatch):
    """A run whose trace directory holds the recorded file."""
    d = tmp_path / ".bench_trace" / "some_cell" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(RECORDED.read_bytes())
    monkeypatch.setattr(hs, "ROOT", tmp_path)
    obs.reset_registry()
    yield dict(trace=dict(window_s=1.0), workload="some_cell", cfg=CFG,
               peaks=dict(bf16_flops=1e9))
    obs.reset_registry()


def _reader(name):
    return common.load_module(
        Path(__file__).parents[1] / "metrics" / f"{name}.py",
        "test_metric_" + name.replace(".", "_")).read


def test_restore_share_on_the_recorded_file(traced_run):
    # two restore spans of 0.725 and 0.581 ms in a window of 50.87 ms
    assert _reader("restore_share.axk1")(traced_run) \
        == pytest.approx(100.0 * (0.724590 + 0.580719) / 50.871103,
                         rel=1e-4)
    for name in ("restore_share.axk1", "prefill_flops_share"):
        assert _reader(name)(dict(trace=None, workload="some_cell",
                                  cfg=CFG)) is None


def test_cached_share_is_the_stores_own_count_over_the_callers_prompts():
    """The engine's ``serve_kv_prefix_tokens_saved_total`` over the
    prompt tokens of the closed loop's requests that got a first token;
    nothing where no prefix cache ever counted an admission (a ring
    model's engine, or no engine), 0 where it only missed."""
    import numpy as np

    from benchmark.lib.serving import Sent
    from pytorch_distributed_nn_tpu.serve.kv_pool import KVPool
    from pytorch_distributed_nn_tpu.serve.prefix_cache import PrefixCache

    read = _reader("prefix_cached_token_share.axk1")
    obs.reset_registry()
    try:
        doc = np.arange(100, 116, dtype=np.int32)      # four blocks of 4
        prompts = [np.concatenate([doc, q]).astype(np.int32)
                   for q in ([1, 2, 3], [4, 5, 6, 7, 8], [9])]
        sent = [Sent(rec={}, prompt=p, arrivals=[1.0]) for p in prompts]
        sent.append(Sent(rec={}, prompt=prompts[0]))   # never admitted
        run = dict(sent=sent)
        assert read(run) is None                       # nobody counted
        pc = PrefixCache(KVPool(num_blocks=32, block_size=4))
        for i, p in enumerate(prompts):                # miss, hit, hit
            m = pc.admit(f"r{i}", p, len(p) + 2)
            pc.finish_restore(m)
            if i == 0:
                assert read(run) == 0.0                # a miss only
            pc.release(f"r{i}", p)
        # 16 + 16 restored of 19 + 21 + 17 asked
        assert read(run) == pytest.approx(100.0 * 32 / 57)
        assert read(dict(sent=[])) is None
    finally:
        obs.reset_registry()


def test_prefill_flops_pairs_each_execution_with_its_span(traced_run):
    """Three ``jit__serve_prefill`` executions of 13.2, 15.6 and 15.8 us,
    each inside its ``serve/prefill_into`` span: the work is the whole
    prompt's and twice the suffix's behind 16 rows, with the counters'
    pairs a token. Without the routing counters the reader says
    nothing."""
    read = _reader("prefill_flops_share")
    assert read(traced_run) is None
    names = tuple((n, {"kind": "prefill", "layer": "1"}) for n in (
        "moe_calls_total", "moe_picks_total", "moe_held_pairs_total"))
    obs.DeviceCounters(names).publish([3, 22, 4])   # 11 tokens, 4 pairs
    pairs = 4 / 11
    need = costs_axk1.prefill_flops(CFG, 5, 0, pairs) \
        + 2 * costs_axk1.prefill_flops(CFG, 3, 16, pairs)
    secs = (13167 + 15636 + 15792) / 1e9
    assert read(traced_run) == pytest.approx(100.0 * need / (secs * 1e9),
                                             rel=1e-6)


def test_a_dense_decoder_charges_each_prefill_its_own_tokens(traced_run):
    """Mistral's reader on the same file: since PR 49 it pairs as
    A.X-K1's does, so the three executions are charged 5, 3 and 3
    tokens (the restored rows count nothing), not three times the
    window's mean prompt."""
    from benchmark.lib import costs

    cfg = dict(hidden_size=8, intermediate_size=16, num_attention_heads=2,
               num_key_value_heads=1, head_dim=4, num_hidden_layers=2,
               vocab_size=10, torch_dtype="bfloat16",
               program=dict(readers="readers"))
    run = dict(traced_run, cfg=cfg)
    need = costs.decoder_prefill_flops(cfg, 5) \
        + 2 * costs.decoder_prefill_flops(cfg, 3)
    secs = (13167 + 15636 + 15792) / 1e9
    assert _reader("prefill_flops_share")(run) \
        == pytest.approx(100.0 * need / (secs * 1e9), rel=1e-6)
