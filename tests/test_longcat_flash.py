"""LongCat-Flash's language model against its plain reference, on the CPU.

The program (``models/longcat_flash.py``: latent attention with its
expanded and absorbed paths, the shortcut MoE as one expert-parallel
rank's share, routing counters summed on the device) against
``benchmark/configs/longcat_flash_omni_ref.py`` (float32, expanded
attention only, no cache, every held expert over every token), at a
small size with seeded weights, both in float32. Logits are compared,
never sampled tokens. The weights come from the benchmark's own
generator, so the layout check that ties the configuration file to the
program runs here too.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.lib import common, weights  # noqa: E402
from pytorch_distributed_nn_tpu import obs  # noqa: E402
from pytorch_distributed_nn_tpu.config import ModelConfig  # noqa: E402
from pytorch_distributed_nn_tpu.inference.generate import (  # noqa: E402
    _apply_decode_ragged,
    init_cache,
)
from pytorch_distributed_nn_tpu.models import get_model  # noqa: E402
from pytorch_distributed_nn_tpu.nn import mla  # noqa: E402
from pytorch_distributed_nn_tpu.parallel.expert import (  # noqa: E402
    HeldExpertsMoE,
)
from pytorch_distributed_nn_tpu.serve import (  # noqa: E402
    DecodeSpec,
    ServingEngine,
)
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

ref = common.load_module(
    ROOT / "benchmark" / "configs" / "longcat_flash_omni_ref.py",
    "longcat_flash_omni_ref_for_tests")

SEED = 2**31 + 11
VOCAB, LAYERS, ROUTED, ZERO, TOPK = 256, 2, 16, 8, 4
# float32 on both sides, but another order of the same sums (the cache,
# the absorbed product, tokens gathered by expert): a logit of size ~4
# moves by ~1e-5. A wrong term (a missing expert, a wrong scale, a
# rotation off by one position) moves it by 1e-2 or more.
LOGIT_TOL = 2e-4


def _cfg(ep_size: int, ep_rank: int) -> dict:
    """The reference's configuration at the small size, one rank's."""
    return dict(
        hidden_size=64, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, ffn_hidden_size=128, expert_ffn_hidden_size=32,
        n_routed_experts=ROUTED // ep_size, zero_expert_num=ZERO,
        moe_topk=TOPK, routed_scaling_factor=6, num_layers=LAYERS,
        vocab_size=VOCAB, rms_norm_eps=1e-5, rope_theta=1e7,
        torch_dtype="float32",
        expert_parallel=dict(ep_size=ep_size, ep_rank=ep_rank))


def _model(ep_size: int, ep_rank: int):
    """The program's model through its registry, shrunk by ``extra``."""
    mc = ModelConfig(name="longcat_flash", dtype="float32",
                     compute_dtype="float32")
    mc.extra = dict(
        vocab_size=VOCAB, num_layers=LAYERS, d_model=64, num_heads=4,
        num_kv_heads=4, mlp_dim=128, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        expert_mlp_dim=32, num_experts=ROUTED, num_zero_experts=ZERO,
        moe_topk=TOPK, routed_scaling=6.0, ep_size=ep_size,
        ep_rank=ep_rank)
    return get_model(mc)


@pytest.fixture(scope="module")
def rank1():
    """(cfg, model, params) of rank 1 of 4: it holds experts 4..7."""
    cfg, model = _cfg(4, 1), _model(4, 1)
    params = weights.tree(SEED, ref.param_spec(cfg))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    weights.check_layout(params, shapes)   # the spec is the program's tree
    return cfg, model, params


@pytest.fixture(autouse=True)
def _highest():
    """A CPU float32 product is exact enough already; said anyway, as
    the reference says it."""
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n: int, salt: int) -> np.ndarray:
    return np.random.default_rng([SEED, salt]).integers(
        0, VOCAB, size=(n,)).astype(np.int32)


@pytest.mark.parametrize("ep_size,ep_rank", [(1, 0), (4, 1), (4, 3)])
def test_full_forward_logits_match_reference(ep_size, ep_rank):
    cfg, model = _cfg(ep_size, ep_rank), _model(ep_size, ep_rank)
    params = weights.tree(SEED, ref.param_spec(cfg))
    toks = _tokens(50, 1)
    got = model.apply({"params": params}, jnp.asarray(toks)[None])[0]
    want = ref.logits(cfg, SEED, [(toks, 0)])[0]
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL


def test_published_defaults_are_the_registered_sizes():
    """The two registered names carry the published widths; only the
    share differs."""
    whole = get_model(ModelConfig(name="longcat_flash"))
    share = get_model(ModelConfig(name="longcat_flash_ep32"))
    for m in (whole, share):
        assert (m.d_model, m.num_heads, m.q_lora_rank, m.kv_lora_rank,
                m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                m.mlp_dim, m.expert_mlp_dim, m.num_experts,
                m.num_zero_experts, m.moe_topk, m.routed_scaling,
                m.num_layers, m.vocab_size) == (
            6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 512, 256, 12,
            6.0, 28, 131072)
    assert (whole.ep_size, share.ep_size, share.ep_rank) == (1, 32, 0)


@pytest.mark.parametrize("tiles", [(4, 1024), (16, 8), (64, 16)])
def test_expanded_attention_in_query_blocks_is_the_unblocked_one(tiles):
    """Tiles of queries and keys bound the score temporaries and change
    nothing: a query's softmax is over its own row, whatever the blocks
    it is summed in (tolerance: the same float32 sums, 1e-6)."""
    k = jax.random.split(jax.random.key(3), 5)
    B, T, H, dn, dr, dv, r = 2, 40, 4, 16, 8, 16, 16
    args = (jax.random.normal(k[0], (B, T, H, dn)),
            jax.random.normal(k[1], (B, T, H, dr)),
            jax.random.normal(k[2], (B, T, r)),
            jax.random.normal(k[3], (B, T, dr)),
            jax.random.normal(k[4], (r, H, dn + dv)) / 4,
            jnp.broadcast_to(jnp.arange(T)[None], (B, T)))
    whole = mla.expanded_attention(*args, scale=24 ** -0.5, query_block=T,
                                   key_block=T)
    parts = mla.expanded_attention(*args, scale=24 ** -0.5,
                                   query_block=tiles[0], key_block=tiles[1])
    assert np.abs(np.asarray(whole - parts)).max() < 1e-6


def test_absorbed_attention_is_the_expanded_one():
    """``W_kvb`` folded into query and output against K and V expanded
    from the latent: the same products in another order (float32,
    1e-5 on outputs of size ~1)."""
    k = jax.random.split(jax.random.key(4), 5)
    B, S, H, dn, dr, dv, r = 3, 24, 4, 16, 8, 16, 16
    q_nope = jax.random.normal(k[0], (B, 1, H, dn))
    q_rope = jax.random.normal(k[1], (B, 1, H, dr))
    latent = jax.random.normal(k[2], (B, S, r))
    rope_key = jax.random.normal(k[3], (B, S, dr))
    w_kvb = jax.random.normal(k[4], (r, H, dn + dv)) / 4
    depth = jnp.asarray([23, 5, 0])   # each row at its own depth
    want = mla.expanded_attention(
        q_nope, q_rope, latent, rope_key, w_kvb, depth[:, None],
        scale=24 ** -0.5, query_block=8)[:, 0]
    got = mla.absorbed_attention(
        q_nope[:, 0], q_rope[:, 0], latent, rope_key, w_kvb, depth,
        scale=24 ** -0.5)
    assert np.abs(np.asarray(want - got)).max() < 1e-5


def _prefill_then_decode(model, params, rows, slots, max_len, pad):
    """The engine's own programs, by hand, so that logits can be read:
    each of ``rows`` (prompt, continuation) is prefilled alone into a
    padded row cache (``_apply_prefill_at``), inserted into a batch
    cache of ``slots`` rows (``_insert_row``), and all decode together
    (``_apply_decode_ragged``) feeding their continuations. Returns
    per row the logits at its last prompt position and after each fed
    token, and the batch cache."""
    cache = init_cache(model, slots, max_len)
    out = [[] for _ in rows]
    for slot, (prompt, _) in enumerate(rows):
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :len(prompt)] = prompt
        logits, row = engine_mod._apply_prefill_at(
            model, params, init_cache(model, 1, pad), jnp.asarray(tokens),
            jnp.asarray([len(prompt)]), jnp.asarray([0]))
        cache = engine_mod._insert_row(cache, row, slot,
                                       totals=model.device_counter_leaf)
        out[slot].append(np.asarray(logits[0]))
    depth = np.zeros((slots,), np.int32)
    depth[:len(rows)] = [len(p) for p, _ in rows]
    steps = max(len(c) for _, c in rows)
    for t in range(steps):
        fed = np.zeros((slots,), np.int32)
        active = np.zeros((slots,), bool)
        for i, (_, cont) in enumerate(rows):
            if t < len(cont):
                fed[i], active[i] = cont[t], True
        logits, cache = _apply_decode_ragged(
            model, params, cache, jnp.asarray(fed), jnp.asarray(depth),
            token_mask=jnp.asarray(active)[:, None])
        for i in np.flatnonzero(active):
            out[i].append(np.asarray(logits[i]))
        depth = depth + active
    return [np.stack(o) for o in out], cache


def test_prefill_then_decode_through_the_cache_matches_full_forward(rank1):
    """Expanded prefill into a padded row, then absorbed decode rounds
    in a batch with rows at other depths and an empty slot: every
    logit row against the reference's one full forward."""
    cfg, model, params = rank1
    rows = [(_tokens(23, 2), _tokens(9, 3)), (_tokens(7, 4), _tokens(12, 5)),
            (_tokens(32, 6), _tokens(4, 7))]
    got, _ = _prefill_then_decode(model, params, rows, slots=4, max_len=64,
                                  pad=32)
    want = ref.logits(cfg, SEED, [(np.concatenate(r), len(r[0]) - 1)
                                  for r in rows])
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert np.abs(g - w).max() < LOGIT_TOL


def test_a_request_alone_and_in_a_full_padded_batch_agree(rank1):
    """Dropless: nothing a row computes depends on its neighbours, on
    the padding of its prefill or on retired rows (1e-5: the same
    float32 sums, batched otherwise)."""
    _, model, params = rank1
    mine = (_tokens(11, 8), _tokens(6, 9))
    alone, _ = _prefill_then_decode(model, params, [mine], slots=1,
                                    max_len=32, pad=16)
    others = [(_tokens(30, 10), _tokens(8, 11)), mine,
              (_tokens(17, 12), _tokens(2, 13)),
              (_tokens(5, 14), _tokens(8, 15))]
    full, _ = _prefill_then_decode(model, params, others, slots=4,
                                   max_len=64, pad=32)
    assert np.abs(alone[0] - full[1]).max() < 1e-5


def test_a_selection_bias_chooses_and_does_not_weigh(rank1):
    """``b`` moves which experts are picked (the picks differ from the
    unbiased ones) and the weights stay the unbiased scores: logits
    against the reference given the same bias."""
    cfg, model, params = rank1
    rng = np.random.default_rng([SEED, 16])
    bias = rng.normal(0.0, 0.05, size=(LAYERS, ROUTED + ZERO)) \
        .astype(np.float32)
    buffers = {f"layer{i}": {"moe": {"selection_bias": jnp.asarray(bias[i])}}
               for i in range(LAYERS)}
    toks = _tokens(40, 17)
    got = model.apply({"params": params, "buffers": buffers},
                      jnp.asarray(toks)[None])[0]
    want, picks = ref.forward(cfg, SEED, [(toks, 0)], bias=bias)
    _, plain = ref.forward(cfg, SEED, [(toks, 0)])
    assert np.abs(np.asarray(got) - want[0]).max() < LOGIT_TOL
    assert (np.sort(picks[0], -1) != np.sort(plain[0], -1)).any()


def test_the_ranks_parts_add_up_to_the_uncut_layer():
    """The share test: with the experts over four ranks, the four
    ranks' parts, the zero-compute experts' part counted once (every
    rank computes it alike), add up to what the uncut reference gives
    for the layer (float32, 1e-5 on values of size ~1)."""
    d, ff = 64, 32
    whole = _cfg(1, 0)
    spec = dict(ref.param_spec(whole), num_layers=1)
    moe = {k[len("moe/"):]: v for k, v in
           weights.layer(SEED, spec, 0).items() if k.startswith("moe/")}
    a = jax.random.normal(jax.random.key(5), (3, 20, d))
    sizes = tuple(sorted(ref._sizes(whole).items()))
    want, _ = ref._moe(a.reshape(-1, d), moe,
                       jnp.zeros((ROUTED + ZERO,)), sizes, None)

    def rank_part(rank, experts_down):
        def held(w, width):   # the rank's four experts' column blocks
            return w[:, 4 * rank * width:4 * (rank + 1) * width]
        layer = HeldExpertsMoE(
            num_experts=ROUTED, num_zero_experts=ZERO, mlp_dim=ff, k=TOPK,
            routed_scaling=6.0, ep_size=4, ep_rank=rank, token_block=8)
        y, _ = layer.apply({"params": {
            "router": {"kernel": moe["router/kernel"]},
            "experts_gate": held(moe["experts_gate"], ff),
            "experts_up": held(moe["experts_up"], ff),
            "experts_down": held(experts_down, d)}}, a)
        return np.asarray(y).reshape(-1, d)

    parts = sum(rank_part(r, moe["experts_down"]) for r in range(4))
    zero_part = rank_part(0, jnp.zeros_like(moe["experts_down"]))
    assert np.abs(zero_part).max() > 0.01
    assert np.abs(parts - 3 * zero_part - np.asarray(want)).max() < 1e-5


def _serve(engine, prompts, max_new):
    reqs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    engine.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return reqs


def test_served_by_the_engine_with_counters_as_the_reference_counts(rank1):
    """Through ``ServingEngine`` with the defaults ``scripts/serve.py``
    uses (prefix cache on): bucketed prefills, decode rounds with rows
    at different depths and retired rows, a prompt that restores a
    cached prefix. Every served token's logit lies within LOGIT_TOL of
    the reference's best at its position (the benchmark's own check),
    and the device-side counters, published to the registry, equal the
    reference's routing counts over exactly the tokens fed: padding and
    retired rows reach no counter. (Equal to the unit: float32 on both
    sides; a pick flips only on a tie at 1e-7.)"""
    cfg, model, params = rank1
    obs.reset_registry()
    engine = ServingEngine(model, params, max_slots=4, max_seq_len=64,
                           block_size=16, max_queue=64,
                           max_prefills_per_round=2)
    first = _tokens(37, 20)
    prompts = [first, _tokens(9, 21), _tokens(20, 22), _tokens(33, 23),
               _tokens(5, 24)]
    max_new = [6, 11, 3, 8, 14]
    reqs = _serve(engine, prompts, max_new)
    # the first prompt's first 32 tokens are in the prefix store now
    again = np.concatenate([first[:32], _tokens(6, 25)])
    reqs += _serve(engine, [again], [5])
    prompts, max_new = prompts + [again], max_new + [5]
    assert engine.prefix_cache.stats()["prefix_tokens_saved"] == 32
    engine.publish_device_counters()

    seqs = [(np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)]),
             len(p) - 1) for p, r in zip(prompts, reqs)]
    want, picks = ref.forward(cfg, SEED, seqs)
    for w, r in zip(want, reqs):
        served = np.asarray(r.tokens)
        gap = w.max(axis=-1) - w[np.arange(len(served)), served]
        assert gap.max() < LOGIT_TOL

    reg = obs.get_registry().snapshot()

    def read(name, kind, layer):
        return reg.get(f'{name}{{kind="{kind}",layer="{layer}"}}', 0.0)

    first_held = 4
    for layer in range(LAYERS):
        # prefill: the prompt less what the prefix store restored;
        # decode: every served token but the last is fed once
        cached = [0] * (len(prompts) - 1) + [32]
        pre = [pk[layer, c:len(p)] for pk, p, c in
               zip(picks, prompts, cached)]
        dec = np.concatenate([pk[layer, len(p):] for pk, p in
                              zip(picks, prompts)])
        held = lambda x: ((x >= first_held) & (x < first_held + 4))  # noqa
        assert read("moe_calls_total", "prefill", layer) == len(prompts)
        assert read("moe_calls_total", "decode", layer) \
            == len(engine.round_seconds)
        assert read("moe_picks_total", "prefill", layer) \
            == sum(x.size for x in pre)
        assert read("moe_picks_total", "decode", layer) == dec.size
        assert read("moe_zero_expert_picks_total", "prefill", layer) \
            == sum((x >= ROUTED).sum() for x in pre)
        assert read("moe_zero_expert_picks_total", "decode", layer) \
            == (dec >= ROUTED).sum()
        assert read("moe_held_pairs_total", "prefill", layer) \
            == sum(held(x).sum() for x in pre)
        assert read("moe_held_pairs_total", "decode", layer) \
            == held(dec).sum()
        assert read("moe_held_experts_touched_total", "prefill", layer) \
            == sum(len(np.unique(x[held(x)])) for x in pre)


@pytest.mark.parametrize("count", [True, False])
def test_insert_row_sums_the_declared_leaf_and_no_other(count):
    """The engine sums the one leaf the model declares as running
    totals. Any other 1-D leaf is per slot, like every leaf of more
    dimensions: written to its slot, not added and broadcast. A
    further branch row of one prefill (``count`` False) leaves the
    totals as they are."""
    batch = {"kv": jnp.zeros((4, 8, 2)), "per_slot": jnp.zeros((4,)),
             "totals": jnp.arange(5, dtype=jnp.uint32),
             "index": jnp.zeros((), jnp.int32)}
    row = {"kv": jnp.ones((1, 8, 2)), "per_slot": jnp.full((1,), 7.0),
           "totals": jnp.full((5,), 2, jnp.uint32),
           "index": jnp.full((), 3, jnp.int32)}
    out = engine_mod._insert_row(batch, row, 2, totals=("totals",),
                                 count=count)
    assert np.asarray(out["per_slot"]).tolist() == [0.0, 0.0, 7.0, 0.0]
    assert np.asarray(out["kv"]).sum(axis=(1, 2)).tolist() == [0, 0, 16, 0]
    assert np.asarray(out["totals"]).tolist() \
        == [2 * count + i for i in range(5)]
    assert int(out["index"]) == 0


def test_a_prefill_with_two_branch_rows_is_counted_once(rank1):
    """A sampled request's one prefill is inserted into a row for each
    of its branches; its picks join the totals once."""
    _, model, params = rank1
    obs.reset_registry()
    engine = ServingEngine(model, params, max_slots=4, max_seq_len=64,
                           block_size=16)
    req = engine.submit(_tokens(21, 30), 3, decode=DecodeSpec(
        temperature=0.9, top_k=20, best_of=2, n=2, seed=5))
    engine.run_until_idle()
    assert req.state == "done" and len(req.n_best) == 2
    engine.publish_device_counters()
    reg = obs.get_registry().snapshot()
    for layer in range(LAYERS):
        assert reg[f'moe_picks_total{{kind="prefill",layer="{layer}"}}'] \
            == 21 * TOPK
