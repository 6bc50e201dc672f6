"""LFM2's mixture-of-experts language model against its plain reference,
on the CPU.

The program (``models/lfm2_moe.py``: pre-norm blocks whose operator is
``nn/short_conv.py``'s gated short convolution or grouped-query
attention with q/k norms, chosen from a list; a dense feed-forward in
the leading layers, then ``HeldExpertsMoE`` with sigmoid scores; a tied
head) against ``benchmark/configs/lfm2_8b_a1b_ref.py`` (float32, no
cache, every expert over every token), at a small size that keeps the
pattern: two dense convolution layers, then ``[attention, conv, conv]``
and ``[attention, conv]``. Logits are compared, never sampled
tokens. The weights are drawn by the program's own initialisers. One
test ties the reference's operators to the published code
(``transformers``' ``Lfm2ForCausalLM``, the dense sibling).
"""

import functools
import importlib
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
from pytorch_distributed_nn_tpu.models import get_model  # noqa: E402
from pytorch_distributed_nn_tpu.models.lfm2_moe import LAYER_TYPES  # noqa: E402
from pytorch_distributed_nn_tpu.nn import attention  # noqa: E402
from pytorch_distributed_nn_tpu.nn.mamba import CausalConv1d  # noqa: E402
from pytorch_distributed_nn_tpu.ops.pallas import (  # noqa: E402
    prefix_attention as pa,
)
from pytorch_distributed_nn_tpu.serve import ServingEngine  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

# (the package's ``generate`` is the function; this is its module)
gen = importlib.import_module(
    "pytorch_distributed_nn_tpu.inference.generate")
ref = common.load_module(
    ROOT / "benchmark" / "configs" / "lfm2_8b_a1b_ref.py",
    "lfm2_8b_a1b_ref_for_tests")

SEED = 2**31 + 46
VOCAB, D_MODEL, HEADS, KV, MLP = 256, 64, 4, 2, 128
EXPERTS, TOPK, EXPERT_MLP, DENSE = 8, 2, 32, 2
TYPES = ("conv", "conv", "full_attention", "conv", "conv",
         "full_attention", "conv")
LAYERS = len(TYPES)
# float32 on both sides, but another order of the same sums: the tail
# carried through the cache and gathered, attention against the cached
# rows, the experts' pairs sorted and gathered. Logits are of size up to
# ~4 and move by 2e-6 to 4e-6. A wrong term (a tail one position off,
# padding let into the tail, a tail not carried) moves them by 0.5 and
# more (test_a_fault_in_how_the_tail_is_carried_...).
LOGIT_TOL = 5e-5


def _cfg(dtype: str = "float32", **over) -> dict:
    """The reference's configuration at the small size."""
    return dict(dict(
        hidden_size=D_MODEL, num_attention_heads=HEADS,
        num_key_value_heads=KV, intermediate_size=MLP,
        moe_intermediate_size=EXPERT_MLP, num_hidden_layers=LAYERS,
        layer_types=list(TYPES), num_dense_layers=DENSE,
        num_experts=EXPERTS, num_experts_per_tok=TOPK,
        norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1, conv_L_cache=3, conv_bias=False,
        norm_eps=1e-5, rope_theta=1000000.0, vocab_size=VOCAB,
        torch_dtype=dtype), **over)


def _model(dtype: str = "float32", **over):
    """The program's model through its registry, shrunk by ``extra``."""
    mc = ModelConfig(name="lfm2_8b_a1b", dtype=dtype, compute_dtype=dtype)
    mc.extra = dict(dict(
        vocab_size=VOCAB, num_layers=LAYERS, d_model=D_MODEL,
        num_heads=HEADS, num_kv_heads=KV, mlp_dim=MLP,
        expert_mlp_dim=EXPERT_MLP, num_experts=EXPERTS, moe_topk=TOPK,
        num_dense_layers=DENSE, layer_types=TYPES,
        rope_theta=1000000.0, norm_eps=1e-5), **over)
    return get_model(mc)


def _init(model):
    """The program's own initialisers' draw; the table scaled so that
    the tied head's logits are of size ~1."""
    params = jax.jit(lambda: model.init(
        jax.random.key(SEED & 0x7FFFFFFF), jnp.zeros((1, 1), jnp.int32),
        train=False)["params"])()
    params["tok_embed"]["table"] = params["tok_embed"]["table"] * 8.0
    return params


def _ref_logits(cfg, params, seqs, quantize=None, bias=None):
    """The reference on the program's weights, by name."""
    flat = weights.named_leaves(params)
    top = {k: v for k, v in flat.items() if not k.startswith("layer")}
    return ref.forward(cfg, top, lambda i: ref._sub(flat, f"layer{i}"),
                       seqs, quantize, bias)[0]


@pytest.fixture(scope="module")
def served():
    """(cfg, model, params) in float32."""
    model = _model()
    return _cfg(), model, _init(model)


@pytest.fixture(autouse=True)
def _highest():
    """A CPU float32 product is exact enough already; said anyway, as
    the reference says it."""
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n: int, salt: int) -> np.ndarray:
    return np.random.default_rng([SEED, salt]).integers(
        0, VOCAB, size=(n,)).astype(np.int32)


def test_the_spec_is_the_programs_tree(served):
    """The benchmark's generator gives the program's layout leaf for
    leaf: the convolution layers before experts as ``layer<j>``, the
    attention layers as ``attn<j>`` and the leading dense layers as
    ``dense<j>`` among the top leaves, the table under a name the
    generator draws as a kernel; and a convolution has no bias leaf."""
    cfg, model, params = served
    weights.check_layout(jax.eval_shape(
        lambda: weights.tree(SEED, ref.param_spec(cfg))), params)
    assert [n for n, _, _ in model._layers()] == [
        "dense0", "dense1", "attn0", "layer0", "layer1", "attn1",
        "layer2"]
    assert [tuple(x) for x in ref.layer_kinds(cfg)] == list(model._layers())
    assert set(params["layer0"]["conv"]["conv"]) == {"kernel"}
    assert params["layer0"]["conv"]["conv"]["kernel"].shape == (3, D_MODEL)
    assert "table" in params["tok_embed"]


def test_the_published_order_builds_the_right_operator_a_layer():
    """``layer_types`` as published is irregular at its end (attention
    at 2, 6, 10, 14, 18 and then 21): the 24 layers get their operator
    from the list and their feed-forward from ``num_dense_layers``, and
    the cell's 14 are the list's head."""
    shapes = jax.eval_shape(lambda: _model(
        num_layers=24, layer_types=LAYER_TYPES).init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32),
            train=False))["params"]
    attn = [i for i, t in enumerate(LAYER_TYPES) if t == "full_attention"]
    assert attn == [2, 6, 10, 14, 18, 21]
    layers = _model(num_layers=24, layer_types=LAYER_TYPES)._layers()
    assert [i for i, (_, a, _) in enumerate(layers) if a] == attn
    assert [s for _, _, s in layers] == [False] * 2 + [True] * 22
    for name, attention, sparse in layers:
        assert ("attn" in shapes[name]) == attention
        assert ("conv" in shapes[name]) == (not attention)
        assert ("moe" in shapes[name]) == sparse
        assert ("ffn" in shapes[name]) == (not sparse)
    head = _model(num_layers=14, layer_types=LAYER_TYPES)._layers()
    assert head == layers[:14]
    assert sum(a for _, a, _ in head) == 3 and sum(s for *_, s in head) == 12
    with pytest.raises(ValueError, match="layer_types"):
        _model(num_layers=25, layer_types=LAYER_TYPES)._layers()


def test_full_forward_logits_match_reference(served):
    cfg, model, params = served
    toks = _tokens(300, 1)
    got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(toks)[None])[0]
    want = _ref_logits(cfg, params, [(toks, 0)])[0]
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL


_prefill = jax.jit(engine_mod._apply_prefill_at, static_argnums=(0,))


def _tail_map(cache, f):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: f(x)
        if getattr(path[-1], "key", "") == "conv_tail" else x, cache)


def _prefill_then_decode(model, params, toks, prompt_len, pad, max_len,
                         real=None, between=None):
    """A padded prefill of ``toks[:prompt_len]`` into a fresh cache of one
    row, then the rest a token a round, as the engine's programs apply
    the model. Returns the logits of every position from the prompt's
    last on. ``real``: how many of the fed positions the prefill is told
    are real (default ``prompt_len``); ``between`` changes the cache at
    the hand-over from the prefill to the rounds (the faults)."""
    cache = gen.init_cache(model, 1, max_len)
    fed = np.zeros((1, pad), np.int32)
    fed[0, :prompt_len] = toks[:prompt_len]
    first, cache = _prefill(
        model, params, cache, jnp.asarray(fed),
        jnp.asarray([prompt_len if real is None else real], jnp.int32),
        jnp.zeros((1,), jnp.int32))
    if between is not None:
        cache = between(cache)
    rows = [np.asarray(first[0])]
    for p in range(prompt_len, len(toks)):
        logits, cache = gen.decode_step_ragged(
            model, params, cache, jnp.asarray(toks[p:p + 1]),
            jnp.asarray([p], jnp.int32),
            token_mask=jnp.ones((1, 1), bool))
        rows.append(np.asarray(logits[0]))
    return np.stack(rows)


def test_padded_prefill_then_decode_matches_the_full_forward(served):
    """A prompt of 203 tokens padded to 256, then 40 decode rounds
    through the cache, against the reference's full forward over all 243:
    logits, in float32 (LOGIT_TOL and its reason above)."""
    cfg, model, params = served
    toks = _tokens(243, 2)
    got = _prefill_then_decode(model, params, toks, 203, 256, 256)
    want = _ref_logits(cfg, params, [(toks, 202)])[0]
    assert got.shape == want.shape == (41, VOCAB)
    assert np.abs(got - want).max() < LOGIT_TOL


@pytest.mark.parametrize("fault", ["tail_one_off", "padding_let_through",
                                   "tail_zeroed_at_hand_over"])
def test_a_fault_in_how_the_tail_is_carried_fails_the_tolerance(
        served, fault):
    """What LOGIT_TOL is held against: the carried inputs one position
    off, the bucket's padded positions let through to the tail (the
    prefill told that all 256 fed positions are real), the tail zeroed
    where the prefilled row is handed to the rounds. A tail reaches two
    positions, so each is read in the two rounds after the prefill, where
    it moves a logit by many thousand tolerances (asked here: a
    thousand); the rows the attention layers hold carry it on."""
    cfg, model, params = served
    toks = _tokens(243, 2)
    want = _ref_logits(cfg, params, [(toks, 202)])[0]
    kw = {
        "tail_one_off": dict(between=lambda c: _tail_map(
            c, lambda x: jnp.roll(x, 1, axis=1))),
        "padding_let_through": dict(real=256),
        "tail_zeroed_at_hand_over": dict(between=lambda c: _tail_map(
            c, jnp.zeros_like)),
    }[fault]
    got = _prefill_then_decode(model, params, toks, 203, 256, 256, **kw)
    assert np.abs(got[1:3] - want[1:3]).max() > 1000 * LOGIT_TOL


def _tails(cache):
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", "") == "conv_tail"}


def _prefill_row(model, params, fed, n, max_len=256):
    """The cache of one row after a prefill of ``fed``, whose first ``n``
    tokens are real."""
    _, cache = _prefill(
        model, params, gen.init_cache(model, 1, max_len),
        jnp.asarray(fed)[None], jnp.asarray([n], jnp.int32),
        jnp.zeros((1,), jnp.int32))
    return cache


def test_padding_and_inactive_rows_leave_the_tail_bit_for_bit(served):
    """A prompt of 203 real tokens in a bucket of 256 leaves every tail
    the same bit for bit whatever the 53 padded positions hold, and as
    the 203 fed alone leave it; the first layer's is the gated inputs of
    positions 201 and 202, computed by hand. A prompt of one token leaves
    a zero and its own. A decode round leaves an inactive row's tails as
    they were, while the active row's move."""
    _, model, params = served
    toks = _tokens(256, 4)
    alone = _prefill_row(model, params, toks[:203], 203)
    padded = _prefill_row(model, params, toks, 203)
    other = _prefill_row(
        model, params, np.concatenate([toks[:203], _tokens(53, 6)]), 203)
    a, p, o = (_tails(x) for x in (alone, padded, other))
    assert len(a) == 5 and all(v.shape == (1, 2, D_MODEL)
                               for v in a.values())
    assert all(np.abs(v).max() > 0 for v in a.values())
    for name in a:
        assert np.array_equal(p[name], o[name]), name
        assert np.abs(a[name] - p[name]).max() < 1e-5, name
    w = params["dense0"]
    x = params["tok_embed"]["table"][toks[201:203]]
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * w["operator_norm"]["scale"]
    gate_in, _, z = jnp.split(u @ w["conv"]["in_proj"]["kernel"], 3, -1)
    assert np.abs(p["dense0/conv/conv_tail"][0]
                  - np.asarray(gate_in * z)).max() < 1e-6
    one = _tails(_prefill_row(model, params, toks, 1))
    assert (one["dense0/conv/conv_tail"][0, 0] == 0).all()
    assert np.abs(one["dense0/conv/conv_tail"][0, 1]).max() > 0

    # two rows in one batch cache: row 0 active, row 1 not
    batch = gen.init_cache(model, 2, 256)
    for slot in range(2):
        batch = engine_mod._insert_row(batch, padded, slot,
                                       totals=model.device_counter_leaf)
    before = _tails(batch)
    _, after = gen.decode_step_ragged(
        model, params, batch, jnp.asarray(toks[203:205]),
        jnp.asarray([203, 203], jnp.int32),
        token_mask=jnp.asarray([[True], [False]]))
    after = _tails(after)
    for name in before:
        assert np.array_equal(before[name][1], after[name][1]), name
        assert not np.array_equal(before[name][0], after[name][0]), name
        # the active row's tail moved one on
        assert np.array_equal(before[name][0, 1], after[name][0, 0]), name


def test_insert_row_overwrites_the_whole_tail_of_a_dirty_slot(served):
    """``_insert_row`` copies a state leaf ``(1, ...)`` over a slot's like
    any other leaf: nothing of the last occupant is left, and a prompt
    shorter than the tail brings zeros for the positions before it."""
    _, model, params = served
    dirty = jax.tree.map(lambda x: jnp.full_like(x, 7),
                         gen.init_cache(model, 3, 256))
    row = _prefill_row(model, params, _tokens(256, 5), 1)
    out = engine_mod._insert_row(dirty, row, 1,
                                 totals=model.device_counter_leaf)
    want, got = _tails(row), _tails(out)
    for name in want:
        assert np.array_equal(got[name][1], want[name][0]), name
        assert (got[name][1, 0] == 0).all(), name
        assert (got[name][0] == 7).all() and (got[name][2] == 7).all()


# -- the router -------------------------------------------------------------

def test_a_selection_bias_chooses_and_does_not_weigh(served):
    """``use_expert_bias``: worked by hand on one layer, a bias on one
    expert a token did not pick makes it a pick (the token's weakest pick
    leaves) and weighs it by its *score* over the sum of the picked
    scores, the bias nowhere in the weight; model-wide, the program given
    the bias in its ``buffers`` agrees with the reference given the same,
    and both leave what they give without it."""
    cfg, model, params = served
    w = params["attn0"]["moe"]
    y = jax.random.normal(jax.random.key(3), (5, D_MODEL))
    p = np.asarray(jax.nn.sigmoid(y @ w["router"]["kernel"]))
    order = np.argsort(-p, axis=-1)
    bias = np.zeros((EXPERTS,), np.float32)
    third = order[0, 2]          # token 0's third best: not a pick of 2
    bias[third] = 1.0            # sigmoid scores lie under 1
    from pytorch_distributed_nn_tpu.parallel.expert import HeldExpertsMoE

    moe = HeldExpertsMoE(num_experts=EXPERTS, mlp_dim=EXPERT_MLP, k=TOPK,
                         scoring="sigmoid", renormalize=True)

    def expert(j, x):
        cols = slice(j * EXPERT_MLP, (j + 1) * EXPERT_MLP)
        return (jax.nn.silu(x @ w["experts_gate"][:, cols])
                * (x @ w["experts_up"][:, cols])) \
            @ w["experts_down"][:, j * D_MODEL:(j + 1) * D_MODEL]

    got, _ = jax.jit(moe.apply)(
        {"params": w, "buffers": {"selection_bias": bias}}, y[None])
    first = order[0, 0]
    s = p[0, first] + p[0, third]
    want0 = p[0, first] / s * expert(first, y[0]) \
        + p[0, third] / s * expert(third, y[0])
    assert np.abs(np.asarray(got[0, 0]) - np.asarray(want0)).max() < 1e-5
    second = order[0, 1]
    s = p[0, first] + p[0, second]
    plain = p[0, first] / s * expert(first, y[0]) \
        + p[0, second] / s * expert(second, y[0])
    assert np.abs(np.asarray(plain - want0)).max() > 1e-3

    toks = _tokens(64, 7)
    sparse = [n for n, _, s in model._layers() if s]
    biases = np.random.default_rng(5).normal(
        0, 0.3, size=(len(sparse), EXPERTS)).astype(np.float32)
    buffers = {n: {"moe": {"selection_bias": jnp.asarray(b)}}
               for n, b in zip(sparse, biases)}
    got = jax.jit(model.apply)({"params": params, "buffers": buffers},
                               jnp.asarray(toks)[None])[0]
    want = _ref_logits(cfg, params, [(toks, 0)], bias=biases)[0]
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL
    without = _ref_logits(cfg, params, [(toks, 0)])[0]
    assert np.abs(want - without).max() > 100 * LOGIT_TOL


# -- heads of 64 through the prefill's attention ------------------------------

def _qkv(T, S, heads=4, kv=2, d=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(11), 3)
    return (jax.random.normal(ks[0], (1, T, heads, d), dtype),
            jax.random.normal(ks[1], (1, S, kv, d), dtype),
            jax.random.normal(ks[2], (1, S, kv, d), dtype))


def test_heads_of_64_attend_blockwise_as_the_dense_routine_does():
    """A prefill of 600 real tokens in a bucket of 1,024 against a row
    of 1,024 at grouped heads of 64, through ``_prefill_attention`` (the
    engine's routine where the dense scores would be large) and through
    the dense routine: the real queries agree and the padding gets zeros;
    and 64 is a head the kernel's dispatcher takes (a whole lane tile or
    half of one), 32 is not."""
    q, k, v = _qkv(1024, 1024)
    positions = jnp.arange(1024)[None]
    lengths = jnp.asarray([600])
    assert attention.prefill_in_tiles(1024, 1024)
    got = attention._prefill_attention(q, k, v, positions, lengths)
    seen = jnp.arange(1024)[None, None, :] <= positions[:, :, None]
    want = attention._cache_attention(q, k, v, seen, q.dtype)
    assert np.abs(np.asarray(got[:, :600] - want[:, :600])).max() < 2e-5
    assert (np.asarray(got[:, 600:]) == 0).all()
    assert pa._kernel_tiles(64, 64, 512, 1024)
    assert not pa._kernel_tiles(32, 32, 512, 1024)


def test_the_kernel_at_heads_of_64_is_the_recurrence():
    """The Pallas kernel, interpreted, at 64-wide grouped heads in bf16
    against the same recurrence in ``jax.numpy``: a suffix of 256 queries
    behind 300 restored rows, the last 56 of them padding."""
    q, k, v = _qkv(256, 1024, dtype=jnp.bfloat16)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    pos = jnp.where(jnp.arange(256) < 200, 300 + jnp.arange(256), -1)[None]
    args = (heads_first(q), heads_first(k), heads_first(v), pos)
    kw = dict(scale=64 ** -0.5, block_q=256, block_k=512)
    got = pa._pallas(*args, **kw, interpret=True)
    want = pa._blockwise(*args, **kw)
    assert got.shape == (1, 4, 256, 64)
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < 2e-2
    assert (np.asarray(got[:, :, 200:], np.float32) == 0).all()


@pytest.mark.parametrize("routine", ["dense", "kernel"])
def test_heads_of_64_lie_two_a_lane_tile_and_decode_as_one_a_row(
        monkeypatch, routine):
    """A decode cache of 64-wide K/V heads holds a position's heads side
    by side in one flat row, ``(B, S, Hkv * 64)``, two of them a lane
    tile: the same bytes in the same order as ``(B, S, Hkv, 64)``. A
    prefill (8 and 5 real tokens of a bucket of 8) and three decode
    rounds at rows of different depths, the rounds through the dense
    routine over the row by head (the CPU's) or through the round's
    kernel, which reads two heads as one of 128 with each query in its
    own head's lanes (told it is the routine, in interpret mode), give
    each row what the uncached causal forward of its own sequence
    gives; what a row has not been fed is not written. Heads of 128 and
    an odd number of heads of 64 are not packed."""
    assert attention.lane_pack(64, 8) == 2
    assert attention.lane_pack(128, 8) == attention.lane_pack(64, 3) == 1
    attn = attention.MultiHeadAttention(
        num_heads=8, head_dim=64, num_kv_heads=4, causal=True, rotary=True,
        use_bias=False, qk_norm=True)
    x = jax.random.normal(jax.random.key(0), (2, 11, 96))
    params = attn.init(jax.random.key(1), x)["params"]
    # the second row's sequence leaves out its prefill's padding
    own = [x[0], jnp.concatenate([x[1, :5], x[1, 8:]])]
    want = [attn.apply({"params": params}, seq[None])[0] for seq in own]
    asked = []
    if routine == "kernel":
        monkeypatch.setattr(
            attention, "round_key_block",
            lambda S, heads, d, dtype: asked.append((S, d)) or 16)
        monkeypatch.setattr(
            attention, "round_attention",
            functools.partial(pa.round_attention, interpret=True))
    cache = attn.init(jax.random.key(1), jnp.zeros((2, 32, 96)),
                      decode=True)["cache"]
    assert cache["cached_key"].shape == (2, 32, 4 * 64)
    depth = np.asarray([0, 0])
    for fed, real in ((x[:, :8], np.asarray([8, 5])),
                      (x[:, 8:9], None), (x[:, 9:10], None),
                      (x[:, 10:11], None)):
        out, mutated = attn.apply(
            {"params": params, "cache": cache}, fed, decode=True,
            cache_positions=jnp.asarray(depth), mutable=["cache"],
            lengths=None if real is None else jnp.asarray(real))
        cache = mutated["cache"]
        for b in range(2):
            n = 1 if real is None else real[b]
            gap = out[b, :n] - want[b][depth[b]:depth[b] + n]
            assert np.abs(np.asarray(gap)).max() < 2e-5
            assert np.abs(np.asarray(out[b, :n])).max() > 0.05
        depth = depth + (1 if real is None else real)
    assert list(depth) == [11, 8]
    for leaf in ("cached_key", "cached_value"):
        rows = np.abs(np.asarray(cache[leaf])).max(axis=-1) > 0
        # (the second row's prefill wrote its bucket's 8, padding too)
        assert rows[0].tolist() == [True] * 11 + [False] * 21
        assert rows[1].tolist() == [True] * 8 + [False] * 24
    assert asked == ([(32, 128)] * 3 if routine == "kernel" else [])


def test_a_convolution_without_bias_has_no_such_leaf():
    x = jax.random.normal(jax.random.key(1), (2, 5, 8))
    tail = jax.random.normal(jax.random.key(2), (2, 2, 8))
    conv = CausalConv1d(3, use_bias=False)
    variables = conv.init(jax.random.key(0), x, tail)
    assert set(variables["params"]) == {"kernel"}
    out, joined = conv.apply(variables, x, tail)
    kern = variables["params"]["kernel"]
    want = sum(kern[j] * joined[:, j:j + 5] for j in range(3))
    assert np.abs(np.asarray(out - want)).max() < 1e-6
    assert set(CausalConv1d(3).init(jax.random.key(0), x, tail)["params"]) \
        == {"kernel", "bias"}


# -- served by the engine --------------------------------------------------

def _serve_all(engine, prompts, max_new):
    reqs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    engine.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return reqs


def _engine(model, params, slots=2, **kw):
    return ServingEngine(model, params, max_slots=slots, max_seq_len=128,
                         block_size=16, max_queue=64,
                         max_prefills_per_round=2, **kw)


def test_served_through_dirty_slots_as_served_alone_with_counters(
        served, caplog):
    """Through ``ServingEngine`` with the defaults ``scripts/serve.py``
    uses (``prefix_cache=True``): two slots, five requests of different
    lengths, so the later ones are admitted rounds apart into slots that
    retired requests left dirty: a re-admitted slot starts from its own
    prefill's tail. Each request's tokens are those it gets served alone
    (an engine of one slot, a request at a time), and every served
    token's logit lies within LOGIT_TOL of the reference's best at its
    position. The engine has no prefix cache and no store, says why
    once, and refuses block export and ingest: the second model that
    declares a state, with no line of its own in the engine. The
    device-side counters, published to the registry, count the real
    tokens fed."""
    cfg, model, params = served
    obs.reset_registry()
    with caplog.at_level("INFO", logger=engine_mod.log.name):
        engine = _engine(model, params)
    said = [r.getMessage() for r in caplog.records
            if "not rows by position" in r.getMessage()]
    assert len(said) == 1 and "no prefix cache" in said[0]
    assert "5 recurrent state" in said[0]
    assert engine.prefix_cache is None and engine._store is None
    with pytest.raises(ValueError, match="5 recurrent state"):
        engine.export_blocks([0])
    with pytest.raises(ValueError, match="5 recurrent state"):
        engine.ingest_blocks(np.arange(16), None)

    prompts = [_tokens(37, 50), _tokens(5, 51), _tokens(41, 52),
               _tokens(50, 53), _tokens(1, 54)]     # buckets 64 and 16
    max_new = [6, 3, 12, 20, 15]
    reqs = _serve_all(engine, prompts, max_new)
    engine.publish_device_counters()
    reg = obs.get_registry().snapshot()

    seqs = [(np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)]),
             len(p) - 1) for p, r in zip(prompts, reqs)]
    want = _ref_logits(cfg, params, seqs)
    for w, r in zip(want, reqs):
        tokens = np.asarray(r.tokens)
        gap = w.max(axis=-1) - w[np.arange(len(tokens)), tokens]
        assert gap.max() < LOGIT_TOL
    alone = _engine(model, params, slots=1)
    for p, n, r in zip(prompts, max_new, reqs):
        assert list(_serve_all(alone, [p], [n])[0].tokens) \
            == list(r.tokens)

    def read(name, kind, layer, attn=None):
        labels = f'kind="{kind}",layer="{layer}"' \
            + (f',attn="{attn}"' if attn else "")
        return reg.get(f"{name}{{{labels}}}", 0.0)

    rounds = len(engine.round_seconds)
    fed = sum(len(s[0]) - len(p) for s, p in zip(seqs, prompts))
    real = sum(len(p) for p in prompts)
    for layer, (_, attention, sparse) in enumerate(ref.layer_kinds(cfg)):
        if attention:
            pre = sum(len(p) * (len(p) + 1) // 2 for p in prompts)
            both = sum(len(s[0]) * (len(s[0]) + 1) // 2 for s in seqs)
            assert read("attn_rows_attended_total", "prefill", layer,
                        "full") == pre
            assert read("attn_rows_attended_total", "decode", layer,
                        "full") == both - pre
            assert read("attn_rows_read_total", "decode", layer,
                        "full") == fed * 128
            assert read("conv_calls_total", "decode", layer) == 0
        else:
            assert read("conv_calls_total", "prefill", layer) \
                == len(prompts)
            assert read("conv_tokens_total", "prefill", layer) == real
            assert read("conv_calls_total", "decode", layer) == rounds
            assert read("conv_tokens_total", "decode", layer) == fed
        if sparse:
            assert read("moe_calls_total", "decode", layer) == rounds
            assert read("moe_picks_total", "decode", layer) == fed * TOPK
            assert read("moe_picks_total", "prefill", layer) == real * TOPK
            # every expert is held: each pick is a pair computed here
            assert read("moe_held_pairs_total", "decode", layer) \
                == fed * TOPK
            assert 0 < read("moe_held_experts_touched_total", "decode",
                            layer) <= rounds * EXPERTS
        else:
            assert read("moe_calls_total", "decode", layer) == 0
    # the two gauges: what of the batch cache is state, what rows
    state = 2 * 5 * 2 * D_MODEL * 4                  # slots, layers, tail
    rows = 2 * 2 * 2 * 128 * KV * (D_MODEL // HEADS) * 4
    assert reg['serve_cache_bytes{leaves="not_by_position"}'] == state
    assert reg['serve_cache_bytes{leaves="by_position"}'] == rows


def test_the_registered_model_is_the_published_configuration():
    """With no override the registry builds the sizes of the catalog's
    row, which ``benchmark/configs/lfm2_8b_a1b.json`` holds but for its
    depth, and the reference reads the same order of layers; at the
    configuration's depth the program's tree is the reference's spec."""
    cfg = common.load_json(ROOT / "benchmark" / "configs"
                           / "lfm2_8b_a1b.json")
    model = get_model(ModelConfig(name="lfm2_8b_a1b"))
    for field, key in (
            ("vocab_size", "vocab_size"), ("d_model", "hidden_size"),
            ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"),
            ("mlp_dim", "intermediate_size"),
            ("expert_mlp_dim", "moe_intermediate_size"),
            ("num_experts", "num_experts"),
            ("moe_topk", "num_experts_per_tok"),
            ("num_dense_layers", "num_dense_layers"),
            ("routed_scaling", "routed_scaling_factor"),
            ("conv_width", "conv_L_cache"), ("rope_theta", "rope_theta"),
            ("norm_eps", "norm_eps"), ("norm_eps", "rms_norm_eps")):
        assert getattr(model, field) == cfg[key], field
    assert list(model.layer_types) == cfg["layer_types"]
    assert model.num_layers == cfg["reduced_from"]["num_hidden_layers"] == 24
    assert cfg["num_hidden_layers"] == 14
    cut = get_model(ModelConfig(name="lfm2_8b_a1b", dtype="bfloat16",
                                extra=dict(num_layers=14)))
    assert [tuple(x) for x in ref.layer_kinds(cfg)] == list(cut._layers())
    shapes = jax.eval_shape(lambda: cut.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    spec = ref.param_spec(cfg)
    assert spec["num_layers"] == 9
    named = {n: tuple(leaf.shape)
             for n, leaf in weights.named_leaves(shapes).items()}
    want = dict(spec["top"])
    for i in range(9):
        want.update({f"layer{i}/{n}": s for n, s in spec["layer"]})
    assert named == {n: tuple(s) for n, s in want.items()}
    # 4.67 B parameters, 9.33 GB in bf16 (the issue's arithmetic)
    total = sum(int(np.prod(s)) for s in named.values())
    assert 4.66e9 < total < 4.68e9


# -- the reference against the published code ------------------------------

def test_reference_operators_are_transformers_lfm2():
    """``Lfm2ForCausalLM`` (the dense sibling, whose ``Lfm2ShortConv``
    and ``Lfm2Attention`` the family shares) given the reference's
    weights yields the reference's logits with every layer dense
    (``num_dense_layers`` = the depth), in float32: the convolution, the
    two gates, the q/k norms, the rotation, the grouped heads, the
    norms' places and the tied head are the published ones. The expert
    layer is not in this version of the library
    (``Lfm2MoeSparseMoeBlock``, >= 4.58): the hand-worked router test
    above holds it."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.models.lfm2 import Lfm2Config, Lfm2ForCausalLM

    types = ["conv", "full_attention", "conv"]
    cfg = _cfg(vocab_size=96, num_hidden_layers=3, layer_types=types,
               num_dense_layers=3)
    hf = Lfm2ForCausalLM(Lfm2Config(
        vocab_size=96, hidden_size=D_MODEL, intermediate_size=MLP,
        num_hidden_layers=3, num_attention_heads=HEADS,
        num_key_value_heads=KV, norm_eps=1e-5, rope_theta=1000000.0,
        conv_bias=False, conv_L_cache=3, block_auto_adjust_ff_dim=False,
        layer_types=types, tie_word_embeddings=True, pad_token_id=0,
        attn_implementation="eager")).float().eval()
    flat = {}
    rng = np.random.default_rng(9)
    kinds = ref.layer_kinds(cfg)
    # (param_spec refuses attention before a dense feed-forward, which
    # no published MoE model has; the operators do not care)
    hd = D_MODEL // HEADS
    shapes = {"operator_norm/scale": (D_MODEL,), "ffn_norm/scale": (D_MODEL,),
              "ffn/gate_proj/kernel": (D_MODEL, MLP),
              "ffn/up_proj/kernel": (D_MODEL, MLP),
              "ffn/down_proj/kernel": (MLP, D_MODEL)}
    conv = {"conv/in_proj/kernel": (D_MODEL, 3 * D_MODEL),
            "conv/conv/kernel": (3, D_MODEL),
            "conv/out_proj/kernel": (D_MODEL, D_MODEL)}
    attn = {"attn/query/kernel": (D_MODEL, HEADS, hd),
            "attn/key/kernel": (D_MODEL, KV, hd),
            "attn/value/kernel": (D_MODEL, KV, hd),
            "attn/q_norm/scale": (hd,), "attn/k_norm/scale": (hd,),
            "attn/out/kernel": (HEADS, hd, D_MODEL)}

    def draw(name, shape):
        if name.endswith("scale"):
            return (1 + 0.1 * rng.normal(size=shape)).astype(np.float32)
        return (rng.normal(size=shape) / np.sqrt(shape[0])
                ).astype(np.float32)

    flat["tok_embed/table"] = (rng.normal(size=(96, D_MODEL)) * 0.3
                               ).astype(np.float32)
    flat["embedding_norm/scale"] = draw("scale", (D_MODEL,))
    for name, attention, _ in kinds:
        for leaf, shape in {**shapes, **(attn if attention else conv)
                            }.items():
            flat[f"{name}/{leaf}"] = draw(leaf, shape)
    t = lambda x: torch.tensor(np.asarray(x, np.float32))  # noqa: E731
    state = {"model.embed_tokens.weight": t(flat["tok_embed/table"]),
             "model.embedding_norm.weight": t(flat["embedding_norm/scale"])}
    for i, (name, attention, _) in enumerate(kinds):
        w = ref._sub(flat, name)
        pre = f"model.layers.{i}."
        state[pre + "operator_norm.weight"] = t(w["operator_norm/scale"])
        state[pre + "ffn_norm.weight"] = t(w["ffn_norm/scale"])
        for ours, theirs in (("gate_proj", "w1"), ("up_proj", "w3"),
                             ("down_proj", "w2")):
            state[pre + f"feed_forward.{theirs}.weight"] = \
                t(w[f"ffn/{ours}/kernel"]).T
        if attention:
            for ours, theirs in (("query", "q_proj"), ("key", "k_proj"),
                                 ("value", "v_proj")):
                state[pre + f"self_attn.{theirs}.weight"] = \
                    t(w[f"attn/{ours}/kernel"].reshape(D_MODEL, -1)).T
            state[pre + "self_attn.out_proj.weight"] = \
                t(w["attn/out/kernel"].reshape(-1, D_MODEL)).T
            state[pre + "self_attn.q_layernorm.weight"] = \
                t(w["attn/q_norm/scale"])
            state[pre + "self_attn.k_layernorm.weight"] = \
                t(w["attn/k_norm/scale"])
        else:
            state[pre + "conv.in_proj.weight"] = \
                t(w["conv/in_proj/kernel"]).T
            state[pre + "conv.out_proj.weight"] = \
                t(w["conv/out_proj/kernel"]).T
            state[pre + "conv.conv.weight"] = \
                t(w["conv/conv/kernel"]).T[:, None, :]
    missing, unexpected = hf.load_state_dict(state, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}
    hf.tie_weights()
    toks = rng.integers(0, 96, size=(45,)).astype(np.int64)
    with torch.no_grad():
        theirs = hf(torch.tensor(toks)[None], use_cache=False,
                    logits_to_keep=0).logits[0].numpy()
    top = {k: jnp.asarray(v) for k, v in flat.items()}
    ours = ref.forward(cfg, top, None, [(toks.astype(np.int32), 0)])[0][0]
    assert np.abs(ours).max() > 1.0
    assert np.abs(ours - theirs).max() < 1e-4
