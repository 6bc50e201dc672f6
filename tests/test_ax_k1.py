"""A.X-K1's language model against its plain reference, on the CPU.

The program (``models/ax_k1.py``: pre-norm blocks with one latent
attention whose rotation is YaRN-scaled, group-limited sigmoid routing
as one expert-parallel rank's share, a shared expert, a leading dense
layer) against ``benchmark/configs/ax_k1_ref.py`` (float32, the expanded
attention under a full causal mask, no cache, every held expert over
every token), at a small size with seeded weights, both in float32.
Logits are compared, never sampled tokens. The weights come from the
benchmark's own generator, so the layout check that ties the
configuration file to the program runs here too.

Also here: the guard of the modules A.X-K1 shares (``nn/mla.py``,
``nn/attention.py``, ``parallel/expert.py``): a LongCat-shaped and a
K-EXAONE-shaped model lower to the serve programs, and have the
parameter trees, cache trees and logits, they had before those modules
gained A.X-K1's fields.
"""

import hashlib
import importlib
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tests"))

import serve_program_digests  # noqa: E402
from benchmark.lib import common, weights  # noqa: E402
from pytorch_distributed_nn_tpu import obs  # noqa: E402
from pytorch_distributed_nn_tpu.config import ModelConfig  # noqa: E402
from pytorch_distributed_nn_tpu.models import get_model  # noqa: E402
from pytorch_distributed_nn_tpu.nn import attention, mla  # noqa: E402
from pytorch_distributed_nn_tpu.parallel.expert import (  # noqa: E402
    HeldExpertsMoE,
)
from pytorch_distributed_nn_tpu.serve import ServingEngine  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

# (the package's ``generate`` is the function; this is its module)
gen = importlib.import_module(
    "pytorch_distributed_nn_tpu.inference.generate")
ref = common.load_module(
    ROOT / "benchmark" / "configs" / "ax_k1_ref.py", "ax_k1_ref_for_tests")

SEED = 2**31 + 35
VOCAB, LAYERS, ROUTED, TOPK, GROUPS, KEEP = 256, 4, 16, 4, 4, 2
# the scaled rotation at the small size: 4 pairs, of which pair 0 keeps
# its frequency, pair 1 is blended and pairs 2, 3 are slowed 32 x
ORIGINAL = 64
# float32 on both sides, but another order of the same sums (the cache,
# the absorbed form, queries in blocks, tokens gathered by expert): a
# logit of size ~4 moves by ~1e-5. A wrong term (a missing expert, a pick
# outside its groups, a weight not renormalised, plain frequencies for
# scaled ones, the scale without m**2, a restored row one off) moves it
# by 1e-2 or more; bf16 in place of float32 by ~3e-2
# (test_bf16_in_place_of_float32_fails_the_tolerance).
LOGIT_TOL = 2e-4


def _cfg(ep_size: int, ep_rank: int, **over) -> dict:
    """The reference's configuration at the small size, one rank's:
    layer 0 dense, three sparse layers of 16 experts in 4 groups."""
    return dict(dict(
        hidden_size=64, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=192, moe_intermediate_size=32,
        num_hidden_layers=LAYERS, first_k_dense_replace=1,
        n_routed_experts=ROUTED // ep_size, num_experts_per_tok=TOPK,
        n_group=GROUPS, topk_group=KEEP, n_shared_experts=1,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=32, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=ORIGINAL,
                          type="yarn"),
        vocab_size=VOCAB, torch_dtype="float32",
        expert_parallel=dict(ep_size=ep_size, ep_rank=ep_rank)), **over)


def _model(ep_size: int, ep_rank: int, dtype: str = "float32", **over):
    """The program's model through its registry, shrunk by ``extra``."""
    mc = ModelConfig(name="ax_k1", dtype=dtype, compute_dtype=dtype)
    mc.extra = dict(dict(
        vocab_size=VOCAB, num_layers=LAYERS, d_model=64, num_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, mlp_dim=192, expert_mlp_dim=32,
        num_experts=ROUTED, moe_topk=TOPK, n_group=GROUPS, topk_group=KEEP,
        rope_original_positions=ORIGINAL, ep_size=ep_size,
        ep_rank=ep_rank), **over)
    return get_model(mc)


@pytest.fixture(scope="module")
def rank1():
    """(cfg, model, params) of rank 1 of 4: it holds experts 4..7, the
    whole of group 1."""
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
    """90 positions: past the 64 the scaled rotation calls original."""
    cfg, model = _cfg(ep_size, ep_rank), _model(ep_size, ep_rank)
    params = weights.tree(SEED, ref.param_spec(cfg))
    toks = _tokens(90, 1)
    got = model.apply({"params": params}, jnp.asarray(toks)[None])[0]
    want = ref.logits(cfg, SEED, [(toks, 0)])[0]
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL


def test_bf16_in_place_of_float32_fails_the_tolerance(rank1):
    """The tolerance is tight enough to tell the precision below the
    stated one: the same model and weights computed in bfloat16 miss the
    float32 reference by a hundred times LOGIT_TOL."""
    cfg, _, params = rank1
    low = _model(4, 1, dtype="bfloat16")
    toks = _tokens(90, 1)
    got = low.apply({"params": jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), params)}, jnp.asarray(toks)[None])
    want = ref.logits(cfg, SEED, [(toks, 0)])[0]
    assert np.abs(np.asarray(got[0], np.float32) - want).max() \
        > 50 * LOGIT_TOL


def test_published_defaults_are_the_registered_sizes():
    """The two registered names carry the published widths; only the
    share differs."""
    whole = get_model(ModelConfig(name="ax_k1"))
    share = get_model(ModelConfig(name="ax_k1_ep16"))
    for m in (whole, share):
        assert (m.d_model, m.num_heads, m.q_lora_rank, m.kv_lora_rank,
                m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                m.mlp_dim, m.expert_mlp_dim, m.num_experts, m.moe_topk,
                m.n_group, m.topk_group, m.num_shared_experts,
                m.routed_scaling, m.rope_theta, m.rope_factor,
                m.rope_original_positions, m.first_k_dense, m.num_layers,
                m.vocab_size, m.norm_eps) == (
            7168, 64, 1536, 512, 128, 64, 128, 18432, 2048, 192, 8, 8, 4,
            1, 2.5, 10000.0, 32.0, 4096, 1, 61, 163840, 1e-6)
    assert (whole.ep_size, share.ep_size, share.ep_rank) == (1, 16, 0)
    kinds = whole._layers()
    assert [k[0] for k in kinds[:3]] == ["dense0", "layer0", "layer1"]
    assert sum(k[1] for k in kinds) == 1


def test_the_benchmark_file_keeps_every_published_width():
    """``benchmark/configs/ax_k1.json`` against the registered name it
    runs (the eight sizes the harness passes and the defaults it does
    not) and against the catalog's entry where this machine has it:
    nothing but depth, experts held and vocabulary differs."""
    cfg = common.load_json(ROOT / "benchmark" / "configs" / "ax_k1.json")
    m = get_model(ModelConfig(name=cfg["program"]["model_name"]))
    rs = cfg["rope_scaling"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["n_group"], cfg["topk_group"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rope_theta"],
            cfg["first_k_dense_replace"], cfg["rms_norm_eps"],
            rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"], rs["mscale"],
            rs["mscale_all_dim"], rs["type"]) == (
        m.d_model, m.num_heads, m.q_lora_rank, m.kv_lora_rank,
        m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.mlp_dim,
        m.expert_mlp_dim, m.moe_topk, m.n_group, m.topk_group,
        m.num_shared_experts, m.routed_scaling, m.rope_theta,
        m.first_k_dense, m.norm_eps, m.rope_factor,
        m.rope_original_positions, m.rope_beta_fast, m.rope_beta_slow,
        m.rope_mscale, m.rope_mscale_all_dim, "yarn")
    ep = cfg["expert_parallel"]
    assert (cfg["n_routed_experts"] * ep["ep_size"], ep["ep_size"],
            ep["ep_rank"]) == (m.num_experts, m.ep_size, m.ep_rank)
    assert ref.layer_kinds(cfg) == list(
        m._layers()[:cfg["num_hidden_layers"]])
    # the model-configs guide's floors
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["reduced_from"]["vocab_size"]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text()
                   .splitlines() if '"A.X-K1"' in line)
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced_from"])
        assert {k: row["config"][k] for k in differs} == cfg["reduced_from"]
        assert cfg["source"] == row["source_url"]


# -- the scaled rotation ---------------------------------------------------

def test_yarn_at_the_published_sizes_is_the_hand_worked_one():
    """``low`` 10, ``high`` 23, ``m**2`` 1.8133, the cos/sin factor 1
    and the 32 frequencies, by hand: pairs 0..10 keep ``10000**(-i/32)``,
    pairs 23..31 turn 32 times slower, and pair 10 + j blends with
    ``g = j / 13``. The program's, and the reference's own."""
    m = get_model(ModelConfig(name="ax_k1_ep16"))
    freqs, factor = m.rotation()
    f = [10000.0 ** (-i / 32.0) for i in range(32)]
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000))) == 23
    want = [f[i] if i <= 10 else f[i] / 32 if i >= 23
            else f[i] / 32 * ((i - 10) / 13) + f[i] * (1 - (i - 10) / 13)
            for i in range(32)]
    assert len(freqs) == 32
    assert np.allclose(freqs, want, rtol=1e-12, atol=0)
    # three of them to their digits
    assert freqs[0] == 1.0
    assert freqs[10] == pytest.approx(0.0562341325, rel=1e-8)
    assert freqs[16] == pytest.approx(
        0.01 / 32 * (6 / 13) + 0.01 * (7 / 13), rel=1e-12)
    assert freqs[31] == pytest.approx(10000.0 ** (-31 / 32) / 32, rel=1e-12)
    assert factor == pytest.approx(1.8133, abs=5e-5)
    assert factor == pytest.approx((0.1 * math.log(32) + 1) ** 2, rel=1e-12)
    cfg = common.load_json(ROOT / "benchmark" / "configs" / "ax_k1.json")
    w, a, m2, low, high = ref.rotation(cfg)
    assert (low, high, a) == (10, 23, 1.0)
    assert np.allclose(w, want, rtol=1e-12, atol=0)
    assert m2 == pytest.approx(factor, rel=1e-12)


def test_no_rope_scaling_is_the_rotation_that_was_there():
    """``freqs`` absent, ``rotary_embedding`` lowers to the text it
    lowered to before it took the argument (the digests below hold that
    for whole programs) and a model with ``rope_factor`` 1 hands the
    attention no frequencies and no factor; power-law frequencies
    passed as ``freqs`` rotate exactly as ``theta`` does."""
    q = jax.random.normal(jax.random.key(1), (2, 9, 3, 8))
    k = jax.random.normal(jax.random.key(2), (2, 9, 1, 8))
    pos = jnp.arange(9)[None] + jnp.asarray([[3], [70]])
    plain = attention.rotary_embedding(q, k, theta=1e4, positions=pos)
    given = attention.rotary_embedding(
        q, k, positions=pos,
        freqs=tuple(1e4 ** (-jnp.arange(0, 4) * 2.0 / 8)))
    for a, b in zip(plain, given):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert _model(1, 0, rope_factor=1.0).rotation() == (None, 1.0)
    # and the scaled frequencies do turn otherwise
    scaled = attention.rotary_embedding(
        q, k, positions=pos,
        freqs=attention.yarn_frequencies(8, 1e4, 32.0, ORIGINAL))
    assert np.abs(np.asarray(scaled[0]) - np.asarray(plain[0])).max() > 0.1


@pytest.mark.parametrize("mscale,all_dim", [(0.7, 1.0), (1.0, 0), (0.5, 0.5)])
def test_a_factor_on_cos_and_sin_has_no_program(mscale, all_dim):
    """``mscale`` other than ``mscale_all_dim`` (the published block has
    them equal) would put their corrections' ratio on cos and sin, and
    ``mscale_all_dim`` 0 the whole correction, as ``transformers`` reads
    the block and as the reference does: the model refuses exactly the
    blocks where the reference's factor is not 1, and where it is 1 the
    scale takes ``mscale_all_dim``'s correction squared."""
    scaling = dict(_cfg(1, 0)["rope_scaling"], mscale=mscale,
                   mscale_all_dim=all_dim)
    cfg = _cfg(1, 0, rope_scaling=scaling, num_hidden_layers=2)
    model = _model(1, 0, rope_mscale=mscale, rope_mscale_all_dim=all_dim,
                   num_layers=2)
    _, a, m2, _, _ = ref.rotation(cfg)
    assert (a != 1.0) == (mscale != all_dim)
    if a != 1.0:
        with pytest.raises(ValueError, match="cos and sin"):
            model.rotation()
        return
    assert model.rotation()[1] == pytest.approx(m2, rel=1e-12)
    params = weights.tree(SEED, ref.param_spec(cfg))
    toks = _tokens(70, 2)
    got = model.apply({"params": params}, jnp.asarray(toks)[None])[0]
    want = ref.logits(cfg, SEED, [(toks, 0)])[0]
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL


# -- the latent attention's fields -----------------------------------------

def test_absorbed_attention_is_the_expanded_one_under_the_scaled_scale():
    """A decode round's absorbed form against the expanded one with the
    softmax scale's factor in it (float32, 1e-5)."""
    B, S, H, dn, dr, dv, r = 3, 24, 4, 16, 8, 16, 16
    ks = jax.random.split(jax.random.key(3), 6)
    q_nope = jax.random.normal(ks[0], (B, H, dn))
    q_rope = jax.random.normal(ks[1], (B, H, dr))
    latent = jax.random.normal(ks[2], (B, S, r))
    rope_key = jax.random.normal(ks[3], (B, S, dr))
    w_kvb = jax.random.normal(ks[4], (r, H, dn + dv)) / 4
    pos = jnp.asarray([5, 23, 0])
    scale = (dn + dr) ** -0.5 * 1.8133
    got = mla.absorbed_attention(q_nope, q_rope, latent, rope_key, w_kvb,
                                 pos, scale=scale)
    want = mla.expanded_attention(q_nope[:, None], q_rope[:, None], latent,
                                  rope_key, w_kvb, pos[:, None],
                                  scale=scale)[:, 0]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_mla_fields_at_their_defaults_and_at_longcats_values():
    """The plain form has no multiplier, rotates at ``rope_theta`` and
    names the query up-projection ``q_b``; LongCat's block passes its two
    multipliers and its leaf name, and its parameter tree is the one it
    had (the digests below hold the programs)."""
    plain = mla.MLAttention(num_heads=4, q_lora_rank=32, kv_lora_rank=16,
                            qk_nope_head_dim=16, qk_rope_head_dim=8,
                            v_head_dim=16)
    assert (plain.q_multiplier, plain.kv_multiplier, plain.rope_freqs,
            plain.score_factor, plain.q_up_name) \
        == (1.0, 1.0, None, 1.0, "q_b")
    x = jax.random.normal(jax.random.key(4), (1, 6, 64))
    names = set(plain.init(jax.random.key(0), x)["params"])
    assert names == {"q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b",
                     "out"}
    mc = ModelConfig(name="longcat_flash", dtype="float32",
                     compute_dtype="float32")
    mc.extra = dict(serve_program_digests._SHAPES["longcat"][1])
    longcat = get_model(mc)
    tree = jax.eval_shape(lambda: longcat.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    assert "q_b_out" in tree["layer0"]["attn0"] \
        and "q_b" not in tree["layer0"]["attn1"]


# -- through the cache -----------------------------------------------------

# the engine's two model programs without the choice of a token, so that
# logits can be read (jitted as the engine jits them)
_prefill = jax.jit(engine_mod._apply_prefill_at, static_argnums=(0,))
_decode = jax.jit(gen._apply_decode_ragged, static_argnums=(0,))


def _prefill_then_decode(model, params, rows, slots, max_len, pad):
    """The engine's own programs, by hand, so that logits can be read:
    each of ``rows`` (prompt, continuation) is prefilled alone into a
    padded row cache (``_apply_prefill_at``, the expanded attention),
    inserted into row ``slot`` of a batch cache (``_insert_row``), and
    all decode together (``_apply_decode_ragged``, the absorbed
    attention) feeding their continuations. Returns per row the logits
    at its last prompt position and after each fed token."""
    cache = gen.init_cache(model, slots, max_len)
    out = [[] for _ in rows]
    for slot, (prompt, _) in enumerate(rows):
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :len(prompt)] = prompt
        logits, row = _prefill(
            model, params, gen.init_cache(model, 1, pad),
            jnp.asarray(tokens), jnp.asarray([len(prompt)]),
            jnp.asarray([0]))
        cache = engine_mod._insert_row(cache, row, slot,
                                       totals=model.device_counter_leaf)
        out[slot].append(np.asarray(logits[0]))
    depth = np.zeros((slots,), np.int32)
    depth[:len(rows)] = [len(p) for p, _ in rows]
    for t in range(max(len(c) for _, c in rows)):
        fed = np.zeros((slots,), np.int32)
        active = np.zeros((slots,), bool)
        for i, (_, cont) in enumerate(rows):
            if t < len(cont):
                fed[i], active[i] = cont[t], True
        logits, cache = _decode(
            model, params, cache, jnp.asarray(fed), jnp.asarray(depth),
            token_mask=jnp.asarray(active)[:, None])
        for i in np.flatnonzero(active):
            out[i].append(np.asarray(logits[i]))
        depth = depth + active
    return [np.stack(o) for o in out]


def test_prefill_then_decode_through_the_cache_matches_full_forward(rank1):
    """Requests of 3 to 100 positions (the longest past the 64 original
    positions of the scaled rotation) in a batch with rows at other
    depths and an empty slot: every logit row against the reference's
    one full forward. The scaled frequencies and ``m**2`` reach the
    expanded prefill and the absorbed decode alike."""
    cfg, model, params = rank1
    rows = [(_tokens(2, 2), _tokens(1, 3)), (_tokens(40, 4), _tokens(9, 5)),
            (_tokens(70, 6), _tokens(30, 7))]
    got = _prefill_then_decode(model, params, rows, slots=4, max_len=128,
                               pad=128)
    want = ref.logits(cfg, SEED, [(np.concatenate(r), len(r[0]) - 1)
                                  for r in rows])
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert np.abs(g - w).max() < LOGIT_TOL


# -- the router ------------------------------------------------------------

def _moe_weights():
    whole = _cfg(1, 0)
    layer = weights.layer(SEED, ref.param_spec(whole), 0)
    return whole, ref._sub(layer, "moe"), ref._sub(layer, "shared_expert"), \
        layer["post_attn_norm/scale"]


def _rank_part(moe, rank, a, ep_size=4, bias=None, **fields):
    d, ff, held = 64, 32, ROUTED // ep_size

    def cols(w, width):   # the rank's experts' column blocks
        return w[:, held * rank * width:held * (rank + 1) * width]
    layer = HeldExpertsMoE(
        num_experts=ROUTED, mlp_dim=ff, k=TOPK, routed_scaling=2.5,
        ep_size=ep_size, ep_rank=rank, token_block=8,
        **dict(dict(scoring="sigmoid", renormalize=True, n_group=GROUPS,
                    topk_group=KEEP), **fields))
    variables = {"params": {
        "router": {"kernel": moe["router/kernel"]},
        "experts_gate": cols(moe["experts_gate"], ff),
        "experts_up": cols(moe["experts_up"], ff),
        "experts_down": cols(moe["experts_down"], d)}}
    if bias is not None:
        variables["buffers"] = {"selection_bias": jnp.asarray(bias)}
    y, counts = layer.apply(variables, a)
    return np.asarray(y).reshape(-1, d), np.asarray(counts)


def test_the_ranks_parts_add_up_to_the_uncut_layer():
    """The share test: with the 16 experts (4 groups) over four ranks,
    the four ranks' routed parts and the shared expert counted once
    (every rank computes it alike) add up to what the uncut reference
    gives for the layer's FFN (float32, 1e-5 on values of size ~1)."""
    whole, moe, shared, _ = _moe_weights()
    a = jax.random.normal(jax.random.key(5), (3, 20, 64))
    flat = a.reshape(-1, 64)
    sizes = tuple(sorted(ref._sizes(whole).items()))
    # the reference normalises inside ``_moe``: a gain of ones and eps 0
    # over tokens scaled to unit mean square is the identity
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True))
    u, routed, _ = ref._moe(flat, jnp.ones((64,)), moe,
                            jnp.zeros((ROUTED,)), sizes, 0.0, None)
    assert np.abs(np.asarray(u) - np.asarray(flat)).max() < 1e-6
    shared_once = np.asarray(ref._swiglu(flat, ref._prep(shared, None)))
    want = np.asarray(routed) + shared_once
    parts = [_rank_part(moe, r, flat.reshape(3, 20, 64))[0]
             for r in range(4)]
    assert all(np.abs(p).max() > 0.01 for p in parts)
    assert np.abs(sum(parts) + shared_once - want).max() < 1e-5


def _router_scores(moe, x):
    return np.asarray(jax.nn.sigmoid(
        x.reshape(-1, 64) @ moe["router/kernel"].astype(jnp.float32)))


def _picks_of(moe, x, bias=None, **fields):
    """Which experts each token picked and how it weighed them, read off
    the layer itself: every expert is held (one rank) and expert j is
    made to return ``e_j`` (gate and up read input dim 0 alone, which is
    1 for every token and which the router does not see), so a token's
    output dim j is the weight of its pick j, 0 if unpicked. Returns
    those weights (N, routed), the layer's counts and the scores."""
    d, ff = 64, 32
    probe = {"router/kernel": moe["router/kernel"].astype(jnp.float32)
             .at[0].set(0.0),
             "experts_gate": jnp.zeros((d, ROUTED * ff)).at[0].set(1.0),
             "experts_up": jnp.zeros((d, ROUTED * ff)).at[0].set(1.0)}
    down = np.zeros((ff, ROUTED * d), np.float32)
    for j in range(ROUTED):
        down[:, j * d + j] = 1.0 / (ff * float(jax.nn.silu(1.0)))
    probe["experts_down"] = jnp.asarray(down)
    y, counts = _rank_part(probe, 0, x, ep_size=1, bias=bias, **fields)
    return y[:, :ROUTED], counts, _router_scores(probe, x)


def test_group_limited_picks_stay_in_the_kept_groups():
    """No token's picks leave its ``topk_group`` groups, the groups kept
    are those whose two best scores add up highest, the picks are the
    best of the kept groups' experts, and the weights are the picked
    scores renormalised over all picks: by hand in numpy, against the
    weights read off the layer. The fifth count is the groups the picks
    fell in."""
    _, moe, _, _ = _moe_weights()
    x = jax.random.normal(jax.random.key(6), (2, 40, 64)).at[..., 0].set(1.0)
    got, counts, scores = _picks_of(moe, x)
    size = ROUTED // GROUPS
    by_group = scores.reshape(-1, GROUPS, size)
    two = np.sort(by_group, -1)[..., -2:].sum(-1)
    kept = np.argsort(-two, -1)[:, :KEEP]
    masked = np.zeros_like(scores)
    for t, groups in enumerate(kept):
        for g in groups:
            masked[t, g * size:(g + 1) * size] = scores[t, g * size:
                                                        (g + 1) * size]
    picks = np.argsort(-masked, -1)[:, :TOPK]
    picked = np.take_along_axis(scores, picks, -1)
    want = np.zeros_like(scores)
    np.put_along_axis(want, picks,
                      2.5 * picked / picked.sum(-1, keepdims=True), -1)
    assert np.abs(got - want).max() < 1e-5
    groups_of = [set(p // size) for p in picks]
    assert all(g <= set(k) for g, k in zip(groups_of, kept))
    assert counts.shape == (5,)
    assert counts[4] == sum(len(g) for g in groups_of)
    assert counts[4] <= KEEP * len(picks)
    # the limit binds: a free top-k would have left the groups somewhere
    free = np.argsort(-scores, -1)[:, :TOPK]
    assert (np.sort(free, -1) != np.sort(picks, -1)).any()


def test_a_selection_bias_moves_the_choice_and_not_the_weights():
    """``b`` joins the scores that choose groups and experts; the
    weights stay the unbiased scores of whatever was picked."""
    _, moe, _, _ = _moe_weights()
    x = jax.random.normal(jax.random.key(7), (1, 50, 64)).at[..., 0].set(1.0)
    bias = np.random.default_rng([SEED, 40]).normal(
        0.0, 0.2, size=(ROUTED,)).astype(np.float32)
    plain, _, scores = _picks_of(moe, x)
    got, _, _ = _picks_of(moe, x, bias=bias)
    picks = np.asarray(ref.select(jnp.asarray(scores), jnp.asarray(bias),
                                  GROUPS, KEEP, TOPK))
    assert ((got > 0) != (plain > 0)).any()
    picked = np.take_along_axis(scores, picks, -1)
    want = np.zeros_like(scores)
    np.put_along_axis(want, picks,
                      2.5 * picked / picked.sum(-1, keepdims=True), -1)
    assert np.abs(got - want).max() < 1e-5


def test_one_group_is_the_selection_that_was_there():
    """``n_group`` 1 (LongCat's and K-EXAONE's layers) runs the code
    that was there: the same four counts, the free top-k's weights, and
    (the digests below) the same lowered programs."""
    _, moe, _, _ = _moe_weights()
    x = jax.random.normal(jax.random.key(8), (1, 30, 64)).at[..., 0].set(1.0)
    got, counts, scores = _picks_of(moe, x, n_group=1, topk_group=1)
    picks = np.argsort(-scores, -1)[:, :TOPK]
    picked = np.take_along_axis(scores, picks, -1)
    want = np.zeros_like(scores)
    np.put_along_axis(want, picks,
                      2.5 * picked / picked.sum(-1, keepdims=True), -1)
    assert counts.shape == (4,)
    assert np.abs(got - want).max() < 1e-5
    with pytest.raises(ValueError, match="groups"):
        _picks_of(moe, x, n_group=3, topk_group=1)


def test_a_model_wide_selection_bias_matches_the_reference(rank1):
    """Through the whole model: logits against the reference given the
    same bias, and the picks differ from the unbiased ones."""
    cfg, model, params = rank1
    bias = np.random.default_rng([SEED, 41]).normal(
        0.0, 0.2, size=(LAYERS - 1, ROUTED)).astype(np.float32)
    buffers = {f"layer{i}": {"moe": {"selection_bias": jnp.asarray(bias[i])}}
               for i in range(LAYERS - 1)}
    toks = _tokens(40, 42)
    got = model.apply({"params": params, "buffers": buffers},
                      jnp.asarray(toks)[None])[0]
    want, picks = ref.forward(cfg, SEED, [(toks, 0)], bias=bias)
    _, plain = ref.forward(cfg, SEED, [(toks, 0)])
    assert np.abs(np.asarray(got) - want[0]).max() < LOGIT_TOL
    assert (np.sort(picks[0], -1) != np.sort(plain[0], -1)).any()


# -- served by the engine --------------------------------------------------

def _serve(engine, prompts, max_new):
    reqs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    engine.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return reqs


def _gaps(cfg, prompts, reqs):
    """Every served token's gap below the reference's best logit at its
    position (the benchmark's own check), and the reference's picks."""
    seqs = [(np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)]),
             len(p) - 1) for p, r in zip(prompts, reqs)]
    want, picks = ref.forward(cfg, SEED, seqs)
    gaps = [w.max(axis=-1) - w[np.arange(len(r.tokens)),
                               np.asarray(r.tokens)]
            for w, r in zip(want, reqs)]
    return gaps, picks, seqs


def test_served_a_miss_then_hits_of_a_shared_prefix(rank1):
    """Through ``ServingEngine`` with the defaults ``scripts/serve.py``
    uses. A request that misses the prefix store (48 tokens of a shared
    document, then its own question), then, once it has retired and
    donated its blocks, two that begin with the same document: three
    blocks of 16 rows restored by reference from the store
    (``_restore_blocks``), the suffix prefilled against them at
    positions 48.. (scaled rotation at the right positions, latent rows
    and rotated keys from another request), then decode. Every served
    token's logit lies within LOGIT_TOL of the reference's best at its
    position over the *whole* sequence, and the engine says the hits
    were hits."""
    cfg, model, params = rank1
    obs.reset_registry()
    engine = ServingEngine(model, params, max_slots=3, max_seq_len=128,
                           block_size=16, max_queue=64,
                           max_prefills_per_round=2)
    assert engine.prefix_cache is not None and engine._store is not None
    document = _tokens(48, 60)
    first = np.concatenate([document, _tokens(9, 61)])
    reqs = _serve(engine, [first], [5])
    assert engine.completed[-1]["cached_tokens"] == 0
    later = [np.concatenate([document, _tokens(20, 62)]),
             np.concatenate([document, _tokens(3, 63)]),
             _tokens(30, 64)]
    reqs += _serve(engine, later, [12, 30, 4])
    cached = {c["request_id"]: c["cached_tokens"] for c in engine.completed}
    assert [cached[r.request_id] for r in reqs] == [0, 48, 48, 0]
    gaps, _, _ = _gaps(cfg, [first] + later, reqs)
    for g in gaps:
        assert g.max() < LOGIT_TOL


def test_served_with_counters_as_the_reference_counts(rank1):
    """Bucketed prefills, decode rounds with rows at different depths
    and retired rows, slots reused: the device-side counters, published
    to the registry, equal the reference's own counts over exactly the
    tokens fed: its routing with the groups its picks fell in, and the
    pairs inside its masks. (Equal to the unit: float32 on both sides; a
    pick flips only on a tie at 1e-7.)"""
    cfg, model, params = rank1
    obs.reset_registry()
    engine = ServingEngine(model, params, max_slots=3, max_seq_len=64,
                           block_size=16, max_queue=64,
                           max_prefills_per_round=2, prefix_cache=False)
    prompts = [_tokens(37, 50), _tokens(2, 51), _tokens(8, 52),
               _tokens(20, 53), _tokens(5, 54), _tokens(3, 55)]
    max_new = [3, 1, 9, 20, 14, 4]
    reqs = _serve(engine, prompts, max_new)
    engine.publish_device_counters()
    gaps, picks, seqs = _gaps(cfg, prompts, reqs)
    for g in gaps:
        assert g.max() < LOGIT_TOL
    reg = obs.get_registry().snapshot()

    def read(name, kind, layer, attn=None):
        labels = f'kind="{kind}",layer="{layer}"' \
            + (f',attn="{attn}"' if attn else "")
        return reg.get(f"{name}{{{labels}}}", 0.0)

    rounds = len(engine.round_seconds)
    fed = sum(len(s[0]) - len(p) for s, p in zip(seqs, prompts))
    pre = sum(ref.attended_counts(cfg, len(p)) for p in prompts)
    both = sum(ref.attended_counts(cfg, len(s[0])) for s in seqs)
    size = ROUTED // GROUPS
    for layer, (_, dense) in enumerate(ref.layer_kinds(cfg)):
        assert read("attn_rows_attended_total", "prefill", layer,
                    "latent") == pre
        assert read("attn_rows_attended_total", "decode", layer,
                    "latent") == both - pre
        # a decode round scores the row's whole length; a prefill the
        # key tiles its query tiles visit: here one, the bucket's rows
        assert read("attn_rows_read_total", "decode", layer,
                    "latent") == fed * 64
        assert read("attn_rows_read_total", "prefill", layer,
                    "latent") == sum(len(p) * engine_mod._bucket_len(len(p))
                                     for p in prompts)
        if dense:
            assert read("moe_calls_total", "decode", layer) == 0
            assert read("moe_pick_groups_total", "decode", layer) == 0
            continue
        sparse = layer - 1
        pk_pre = [pk[sparse, :len(p)] for pk, p in zip(picks, prompts)]
        pk_dec = np.concatenate([pk[sparse, len(p):]
                                 for pk, p in zip(picks, prompts)])
        held = lambda x: (x >= 4) & (x < 8)  # noqa: E731 - rank 1 of 4
        groups = lambda x: sum(  # noqa: E731
            len(np.unique(row // size)) for row in x)
        assert read("moe_calls_total", "prefill", layer) == len(prompts)
        assert read("moe_calls_total", "decode", layer) == rounds
        assert read("moe_picks_total", "decode", layer) == pk_dec.size
        assert read("moe_held_pairs_total", "prefill", layer) \
            == sum(held(x).sum() for x in pk_pre)
        assert read("moe_held_pairs_total", "decode", layer) \
            == held(pk_dec).sum()
        assert read("moe_held_experts_touched_total", "prefill", layer) \
            == sum(len(np.unique(x[held(x)])) for x in pk_pre)
        assert read("moe_pick_groups_total", "prefill", layer) \
            == sum(groups(x) for x in pk_pre)
        assert read("moe_pick_groups_total", "decode", layer) \
            == groups(pk_dec)
        assert groups(pk_dec) <= KEEP * len(pk_dec)


# -- the modules A.X-K1 shares with LongCat and K-EXAONE -------------------

_PINNED = json.loads((ROOT / "tests" / "data"
                      / "serve_program_digests.json").read_text())


@pytest.mark.parametrize("program", ["prefill", "step"])
@pytest.mark.parametrize("family", ["longcat", "kexaone"])
def test_shared_modules_leave_the_other_families_programs_alone(family,
                                                                program):
    """``MLAttention``'s multipliers, frequencies, scale factor and leaf
    name, ``rotary_embedding``'s ``freqs`` and ``HeldExpertsMoE``'s
    groups are fields and arguments whose other values LongCat and
    K-EXAONE pass or leave: their serve programs lower to the text they
    lowered to at commit 6d4c912, before ISSUE 35, but for LongCat's
    prefill, which ISSUE 36 meant to change
    (``tests/serve_program_digests.py`` says how the file was made)."""
    text = serve_program_digests.lowered(family, program)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PINNED[f"{family}.{program}"]


@pytest.mark.parametrize("family", ["longcat", "kexaone"])
def test_shared_modules_leave_the_other_families_trees_and_logits_alone(
        family):
    """Their parameter trees and cache trees (names, shapes, types) and,
    on ``model.init``'s own weights under a fixed key, their logits are
    what they were at commit 6d4c912 (2e-5: the same float32 program on
    another machine's CPU)."""
    got, want = serve_program_digests.trees(family), _PINNED["trees"][family]
    assert got["params"] == want["params"]
    assert got["cache"] == want["cache"]
    assert np.allclose(got["logits"], want["logits"], rtol=0, atol=2e-5)
    assert got["logits_mean_abs"] == pytest.approx(
        want["logits_mean_abs"], abs=2e-5)
