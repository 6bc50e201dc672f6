"""K-EXAONE's language model against its plain reference, on the CPU.

The program (``models/k_exaone.py``: post-norm blocks, window layers
through a ring cache and full layers through rows by position in one
engine, the sigmoid router as one expert-parallel rank's share, a
shared expert, a leading dense layer) against
``benchmark/configs/k_exaone_236b_ref.py`` (float32, full masks, no
cache, no ring, every held expert over every token), at a small size
with seeded weights, both in float32. Logits are compared, never
sampled tokens. The weights come from the benchmark's own generator, so
the layout check that ties the configuration file to the program runs
here too.
"""

import importlib
import json
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
from pytorch_distributed_nn_tpu.nn import attention  # noqa: E402
from pytorch_distributed_nn_tpu.parallel.expert import (  # noqa: E402
    HeldExpertsMoE,
)
from pytorch_distributed_nn_tpu.serve import ServingEngine  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

# (the package's ``generate`` is the function; this is its module)
gen = importlib.import_module(
    "pytorch_distributed_nn_tpu.inference.generate")
ref = common.load_module(
    ROOT / "benchmark" / "configs" / "k_exaone_236b_ref.py",
    "k_exaone_236b_ref_for_tests")

SEED = 2**31 + 33
VOCAB, LAYERS, WINDOW, ROUTED, TOPK = 256, 8, 8, 16, 4
# float32 on both sides, but another order of the same sums (the cache,
# the ring's rows in another order than the positions, the band in
# blocks, tokens gathered by expert): a logit of size ~4 moves by ~1e-5.
# A wrong term (a missing expert, a weight not renormalised, a ring row
# one off, a full layer rotated) moves it by 1e-2 or more.
LOGIT_TOL = 2e-4


def _cfg(ep_size: int, ep_rank: int) -> dict:
    """The reference's configuration at the small size, one rank's:
    ``LLLG`` over 8 layers, layer 0 dense."""
    return dict(
        hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, intermediate_size=192, moe_intermediate_size=32,
        num_hidden_layers=LAYERS, first_k_dense_replace=1,
        layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
        sliding_windows=[WINDOW, WINDOW, WINDOW, 0] * 2,
        mlp_layer_types=["dense"] + ["sparse"] * (LAYERS - 1),
        num_experts=ROUTED // ep_size, num_experts_per_tok=TOPK,
        num_shared_experts=1, routed_scaling_factor=2.5,
        scoring_func="sigmoid", norm_topk_prob=True, n_group=1,
        topk_group=1, rms_norm_eps=1e-5, rope_theta=1e6, vocab_size=VOCAB,
        torch_dtype="float32",
        expert_parallel=dict(ep_size=ep_size, ep_rank=ep_rank))


def _model(ep_size: int, ep_rank: int):
    """The program's model through its registry, shrunk by ``extra``."""
    mc = ModelConfig(name="k_exaone", dtype="float32",
                     compute_dtype="float32")
    mc.extra = dict(
        vocab_size=VOCAB, num_layers=LAYERS, d_model=64, num_heads=8,
        num_kv_heads=2, head_dim=16, mlp_dim=192, window=WINDOW,
        expert_mlp_dim=32, num_experts=ROUTED, moe_topk=TOPK,
        ep_size=ep_size, ep_rank=ep_rank)
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
    """The two registered names carry the published widths and the
    published pattern; only the share differs."""
    whole = get_model(ModelConfig(name="k_exaone"))
    share = get_model(ModelConfig(name="k_exaone_ep8"))
    for m in (whole, share):
        assert (m.d_model, m.num_heads, m.num_kv_heads, m.head_dim,
                m.window, m.mlp_dim, m.expert_mlp_dim, m.num_experts,
                m.moe_topk, m.num_shared_experts, m.routed_scaling,
                m.rope_theta, m.layer_pattern, m.first_k_dense,
                m.num_layers, m.vocab_size) == (
            6144, 64, 8, 128, 128, 18432, 2048, 128, 8, 1, 2.5, 1e6,
            "LLLG", 1, 48, 153600)
    assert (whole.ep_size, share.ep_size, share.ep_rank) == (1, 8, 0)
    kinds = whole._layers()
    assert [k[0] for k in kinds[:3]] == ["dense0", "layer0", "layer1"]
    assert [bool(k[1]) for k in kinds[:8]] == [True, True, True, False] * 2
    assert sum(k[2] for k in kinds) == 1


def test_the_benchmark_file_keeps_every_published_width():
    """``benchmark/configs/k_exaone_236b.json`` against the registered
    name it runs: the eight sizes the harness passes and the defaults
    it does not."""
    cfg = common.load_json(ROOT / "benchmark" / "configs"
                           / "k_exaone_236b.json")
    m = get_model(ModelConfig(name=cfg["program"]["model_name"]))
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["routed_scaling_factor"],
            cfg["rope_theta"], cfg["sliding_window_pattern"],
            cfg["first_k_dense_replace"], cfg["rms_norm_eps"]) == (
        m.d_model, m.num_heads, m.num_kv_heads, m.head_dim, m.window,
        m.mlp_dim, m.expert_mlp_dim, m.moe_topk, m.num_shared_experts,
        m.routed_scaling, m.rope_theta, m.layer_pattern, m.first_k_dense,
        m.norm_eps)
    ep = cfg["expert_parallel"]
    assert (cfg["num_experts"] * ep["ep_size"], ep["ep_size"],
            ep["ep_rank"]) == (m.num_experts, m.ep_size, m.ep_rank)
    assert [(n, bool(w), d) for n, w, d in ref.layer_kinds(cfg)] == [
        (n, bool(w), d)
        for n, w, d in m._layers()[:cfg["num_hidden_layers"]]]


# -- the ring ------------------------------------------------------------

def _banded(q, k, v, window):
    """Every query against every key under the causal band."""
    t = jnp.arange(q.shape[1])
    band = (t[:, None] - t[None, :] < window)[None]
    return attention.dot_product_attention(q, k, v, causal=True, mask=band)


@pytest.mark.parametrize("window,chunks", [
    (8, [40]),                 # one prefill in blocks of the window
    (8, [16, 1, 1, 1, 1, 1]),  # a prefill, then decode rounds
    (8, [5, 1, 1, 1, 1]),      # shorter than the ring, filling it exactly
    (8, [3, 6, 16, 12, 1]),    # prefills that start mid-ring, uneven
    (4, [1, 1, 1, 1, 1, 1]),   # decode from an empty ring
    (16, [8, 8, 24]),          # whole blocks onto a part-filled ring
])
def test_ring_attention_in_chunks_is_the_banded_attention(window, chunks):
    """Fed in any chunks, through a ring of ``window`` rows, every
    query's output is the banded attention's over the whole sequence
    (1e-5: the same float32 sums over rows in ring order), and the ring
    ends holding the newest position of every residue."""
    n, B, H, Hkv, D = sum(chunks), 2, 4, 2, 8
    ks = jax.random.split(jax.random.key(window + n), 3)
    q = jax.random.normal(ks[0], (B, n, H, D))
    k = jax.random.normal(ks[1], (B, n, Hkv, D))
    v = jax.random.normal(ks[2], (B, n, Hkv, D))
    want = np.asarray(_banded(q, k, v, window))
    ring_k = jnp.zeros((B, window, Hkv, D))
    ring_v = jnp.zeros((B, window, Hkv, D))
    at, got = 0, []
    for T in chunks:
        sl = slice(at, at + T)
        out, ring_k, ring_v = attention._ring_attention(
            q[:, sl], k[:, sl], v[:, sl], ring_k, ring_v,
            jnp.full((B,), at), jnp.full((B,), T), jnp.float32)
        got.append(np.asarray(out))
        at += T
    assert np.abs(np.concatenate(got, axis=1) - want).max() < 1e-5
    for r in range(min(window, n)):
        newest = (n - 1) - ((n - 1 - r) % window)
        assert np.array_equal(np.asarray(ring_k[:, r]),
                              np.asarray(k[:, newest]))


def test_ring_prefill_leaves_padding_out_of_the_ring():
    """A bucketed prefill's padding (positions past ``lengths``) must
    not displace real rows: the ring holds the last ``min(length,
    window)`` real positions, each row of the batch at its own length."""
    B, T, R, Hkv, D = 3, 16, 8, 2, 4
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, T, 4, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    lengths = jnp.asarray([3, 8, 13])
    _, ring_k, _ = attention._ring_attention(
        q, k, v, jnp.zeros((B, R, Hkv, D)), jnp.zeros((B, R, Hkv, D)),
        jnp.zeros((B,), jnp.int32), lengths, jnp.float32)
    for b, n in enumerate([3, 8, 13]):
        for r in range(R):
            newest = (n - 1) - ((n - 1 - r) % R)
            want = np.asarray(k[b, newest]) if newest >= 0 \
                else np.zeros((Hkv, D))
            assert np.array_equal(np.asarray(ring_k[b, r]), want)


@pytest.mark.parametrize("lengths", [None, (29, 7)])
def test_cached_prefill_in_tiles_is_the_dense_one(lengths):
    """A full layer's prefill (8 query heads to a K/V head, no
    rotation, a suffix behind 3 filled rows) runs blockwise and changes
    nothing: a query's softmax is over its own row (1e-6: the same
    float32 sums in tiles). Given ``lengths``, a bucket's padding
    attends to nothing and its rows of the result are zeros."""
    ks = jax.random.split(jax.random.key(9), 3)
    B, T, S = 2, 32, 48
    q = jax.random.normal(ks[0], (B, T, 16, 8))
    k = jax.random.normal(ks[1], (B, S, 2, 8))
    v = jax.random.normal(ks[2], (B, S, 2, 8))
    positions = (3 + jnp.arange(T))[None]
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    whole = attention._cache_attention(q, k, v, mask, jnp.float32)
    tiled = attention._prefill_attention(
        q, k, v, positions, None if lengths is None else jnp.asarray(lengths))
    real = np.arange(T)[None] < np.asarray(lengths or (T, T))[:, None]
    gap = np.abs(np.asarray(whole - tiled)).max(axis=(2, 3))
    assert gap[real].max() < 1e-6
    assert np.abs(np.asarray(tiled))[~real].max(initial=0) == 0


@pytest.mark.parametrize("max_len", [16, 64, 512])
def test_a_window_layers_cache_is_its_ring_whatever_the_length(max_len):
    """Window layers keep ``(B, window, kv, head)`` leaves, full layers
    the flat rows by position ``(B, max_len, kv * head)``, side by side
    in one cache tree; the model declares the rings, and no other
    leaf."""
    model = _model(4, 1)
    cache = gen.init_cache(model, 3, max_len)
    (kind, paths), = model.leaves_not_by_position().items()
    assert kind.startswith("ring")
    rings = set(paths)
    assert len(rings) == 2 * 6   # key and value of six window layers
    for name, window, _ in model._layers():
        for leaf in ("cached_key", "cached_value"):
            shape = cache[name]["attn"][leaf].shape
            assert shape == ((3, WINDOW, 2, 16) if window
                             else (3, max_len, 2 * 16))
            assert ((name, "attn", leaf) in rings) == bool(window)


# -- prefill, then decode, through the engine's programs -------------------

# the engine's two model programs without the choice of a token, so that
# logits can be read (jitted as the engine jits them: eager, an 8-layer
# model's loops over experts take minutes)
_prefill = jax.jit(engine_mod._apply_prefill_at, static_argnums=(0,))
_decode = jax.jit(gen._apply_decode_ragged, static_argnums=(0,))
_shared_step = jax.jit(gen._apply_decode, static_argnums=(0,))


def _prefill_then_decode(model, params, rows, slots, max_len, pad,
                         cache=None):
    """The engine's own programs, by hand, so that logits can be read:
    each of ``rows`` (prompt, continuation) is prefilled alone into a
    padded row cache (``_apply_prefill_at``), inserted into row ``slot``
    of a batch cache (``_insert_row``; ``cache`` continues one that
    earlier occupants have used), and all decode together
    (``_apply_decode_ragged``) feeding their continuations. Returns per
    row the logits at its last prompt position and after each fed token,
    and the batch cache."""
    if cache is None:
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
    return [np.stack(o) for o in out], cache


def _against_reference(cfg, rows, got):
    want = ref.logits(cfg, SEED, [(np.concatenate(r), len(r[0]) - 1)
                                  for r in rows])
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert np.abs(g - w).max() < LOGIT_TOL


def test_prefill_then_decode_through_both_caches_matches_full_forward(rank1):
    """Requests of 3 to 40 positions through rings of 8 rows and full
    rows side by side: a ring unfilled to its end (3), filled exactly by
    the prompt (8), filled by the prefill and wrapped by decode, and
    wrapped five times (40), in a batch with rows at other depths and an
    empty slot; then shorter requests into the slots the longer ones
    left, whose rings still hold the old occupants' rows until the
    insert. Every logit row against the reference's one full forward."""
    cfg, model, params = rank1
    rows = [(_tokens(2, 2), _tokens(1, 3)), (_tokens(8, 4), _tokens(9, 5)),
            (_tokens(23, 6), _tokens(17, 7))]
    got, cache = _prefill_then_decode(model, params, rows, slots=4,
                                      max_len=64, pad=32)
    _against_reference(cfg, rows, got)
    again = [(_tokens(5, 8), _tokens(2, 9)), (_tokens(3, 10), _tokens(4, 11)),
             (_tokens(6, 12), _tokens(12, 13))]
    got, _ = _prefill_then_decode(model, params, again, slots=4, max_len=64,
                                  pad=16, cache=cache)
    _against_reference(cfg, again, got)


def test_a_request_alone_and_in_a_full_padded_batch_agree(rank1):
    """Dropless, and a ring is a row's own: nothing a row computes
    depends on its neighbours, on the padding of its prefill or on
    retired rows (1e-5: the same float32 sums, batched otherwise)."""
    _, model, params = rank1
    mine = (_tokens(11, 14), _tokens(14, 15))
    alone, _ = _prefill_then_decode(model, params, [mine], slots=1,
                                    max_len=32, pad=16)
    others = [(_tokens(30, 16), _tokens(8, 17)), mine,
              (_tokens(17, 18), _tokens(2, 19)),
              (_tokens(5, 20), _tokens(20, 21))]
    full, _ = _prefill_then_decode(model, params, others, slots=4,
                                   max_len=64, pad=32)
    assert np.abs(alone[0] - full[1]).max() < 1e-5


@pytest.mark.parametrize("chunk", [0, 8, 12])
def test_shared_index_decode_matches_full_forward(rank1, chunk):
    """``inference/generate``'s path: one shared write index, the prompt
    in one apply or in chunks (12 starts a chunk mid-ring), then a token
    a step, every row's ring wrapped four times."""
    cfg, model, params = rank1
    B, P, n = 2, 24, 14
    seqs = [_tokens(P + n, 22 + b) for b in range(B)]
    toks = jnp.asarray(np.stack(seqs))
    cache = gen.init_cache(model, B, P + n)
    got = []
    for a in range(0, P, chunk or P):
        logits, cache = _shared_step(model, params, cache,
                                     toks[:, a:a + (chunk or P)])
    got.append(np.asarray(logits))
    for t in range(P, P + n - 1):
        logits, cache = _shared_step(model, params, cache,
                                     toks[:, t:t + 1])
        got.append(np.asarray(logits))
    want = ref.logits(cfg, SEED, [(s[:-1], P - 1) for s in seqs])
    for b in range(B):
        assert np.abs(np.stack([g[b] for g in got]) - want[b]).max() \
            < LOGIT_TOL
    out = gen.generate(model, params, toks[:, :P], 6, prefill_chunk=chunk)
    step = ref.logits(cfg, SEED, [(np.asarray(o[:-1]), P - 1) for o in out])
    for o, w in zip(np.asarray(out), step):
        assert (w.max(-1) - w[np.arange(6), o[P:]]).max() < LOGIT_TOL


def test_ragged_generate_keeps_padding_out_of_the_rings(rank1):
    """``generate`` with ``prompt_lengths``: rows of 5 and 19 real
    tokens in one padded prefill; each row's tokens are the reference's
    best (a padded column written into a ring would displace a real
    row)."""
    cfg, model, params = rank1
    lens, P, n = [5, 19], 19, 12
    prompts = [_tokens(L, 30 + i) for i, L in enumerate(lens)]
    padded = np.zeros((2, P), np.int32)
    for i, p in enumerate(prompts):
        padded[i, P - len(p):] = p
    out = np.asarray(gen.generate(model, params, jnp.asarray(padded), n,
                                  prompt_lengths=jnp.asarray(lens)))
    seqs = [(np.concatenate([p, o[P:-1]]), len(p) - 1)
            for p, o in zip(prompts, out)]
    for w, o in zip(ref.logits(cfg, SEED, seqs), out):
        assert (w.max(-1) - w[np.arange(n), o[P:]]).max() < LOGIT_TOL


# -- the router and the share ----------------------------------------------

def test_a_selection_bias_chooses_and_does_not_weigh(rank1):
    """``b`` moves which experts are picked (the picks differ from the
    unbiased ones) and the weights stay the unbiased scores, renormalised
    over the biased picks: logits against the reference given the same
    bias."""
    cfg, model, params = rank1
    rng = np.random.default_rng([SEED, 40])
    bias = rng.normal(0.0, 0.2, size=(LAYERS - 1, ROUTED)) \
        .astype(np.float32)
    buffers = {f"layer{i}": {"moe": {"selection_bias": jnp.asarray(bias[i])}}
               for i in range(LAYERS - 1)}
    toks = _tokens(40, 41)
    got = model.apply({"params": params, "buffers": buffers},
                      jnp.asarray(toks)[None])[0]
    want, picks = ref.forward(cfg, SEED, [(toks, 0)], bias=bias)
    _, plain = ref.forward(cfg, SEED, [(toks, 0)])
    assert np.abs(np.asarray(got) - want[0]).max() < LOGIT_TOL
    assert (np.sort(picks[0], -1) != np.sort(plain[0], -1)).any()


def _moe_weights():
    whole = _cfg(1, 0)
    spec = ref.param_spec(whole)
    layer = weights.layer(SEED, spec, 0)
    return whole, {k[len("moe/"):]: v for k, v in layer.items()
                   if k.startswith("moe/")}, \
        {k[len("shared_expert/"):]: v for k, v in layer.items()
         if k.startswith("shared_expert/")}


def _rank_part(moe, rank, a, ep_size=4, **fields):
    d, ff, held = 64, 32, ROUTED // ep_size

    def cols(w, width):   # the rank's experts' column blocks
        return w[:, held * rank * width:held * (rank + 1) * width]
    layer = HeldExpertsMoE(
        num_experts=ROUTED, mlp_dim=ff, k=TOPK, routed_scaling=2.5,
        ep_size=ep_size, ep_rank=rank, token_block=8,
        **dict(dict(scoring="sigmoid", renormalize=True), **fields))
    y, counts = layer.apply({"params": {
        "router": {"kernel": moe["router/kernel"]},
        "experts_gate": cols(moe["experts_gate"], ff),
        "experts_up": cols(moe["experts_up"], ff),
        "experts_down": cols(moe["experts_down"], d)}}, a)
    return np.asarray(y).reshape(-1, d), np.asarray(counts)


def test_the_ranks_parts_add_up_to_the_uncut_layer():
    """The share test: with the experts over four ranks, the four
    ranks' routed parts and the shared expert counted once (every rank
    computes it alike) add up to what the uncut reference gives for the
    layer's FFN (float32, 1e-5 on values of size ~1)."""
    whole, moe, shared = _moe_weights()
    a = jax.random.normal(jax.random.key(5), (3, 20, 64))
    flat = a.reshape(-1, 64)
    sizes = tuple(sorted(ref._sizes(whole).items()))
    routed, _ = ref._moe(flat, moe, jnp.zeros((ROUTED,)), sizes, None)
    want = np.asarray(routed + ref._swiglu(flat, ref._prep(shared, None)))
    parts = sum(_rank_part(moe, r, a)[0] for r in range(4))
    shared_once = np.asarray(ref._swiglu(flat, ref._prep(shared, None)))
    assert np.abs(parts).max() > 0.01
    assert np.abs(parts + shared_once - want).max() < 1e-5


def test_weights_are_renormalised_over_all_picks_not_the_held_ones():
    """A rank that holds some of a token's picks weighs them by the sum
    of *all* the token's picked scores. Read off the layer itself: every
    held expert is made to return ones(d) (gate and up read input dim 0
    alone, which is 1 for every token and which the router does not
    see), so a token's output is the sum of the weights of its held
    picks. Renormalised over the held picks alone that sum would be the
    scaling, 2.5, for every token that has one."""
    _, moe, _ = _moe_weights()
    d, ff = 64, 32
    x = jax.random.normal(jax.random.key(6), (1, 50, d)).at[..., 0].set(1.0)
    router = moe["router/kernel"].astype(jnp.float32).at[0].set(0.0)
    scores = np.asarray(jax.nn.sigmoid(x.reshape(-1, d) @ router))
    picks = np.argsort(-scores, axis=-1)[:, :TOPK]
    picked = np.take_along_axis(scores, picks, -1)
    want = 2.5 * picked / picked.sum(-1, keepdims=True)
    probe = {"router/kernel": router,
             "experts_gate": jnp.zeros((d, ROUTED * ff)).at[0].set(1.0),
             "experts_up": jnp.zeros((d, ROUTED * ff)).at[0].set(1.0),
             "experts_down": jnp.full(
                 (ff, ROUTED * d), 1.0 / (ff * float(jax.nn.silu(1.0))))}
    for rank in range(4):
        held = (picks >= 4 * rank) & (picks < 4 * rank + 4)
        y, counts = _rank_part(probe, rank, x)
        assert counts[2] == held.sum()
        assert np.abs(y[:, 0] - (want * held).sum(-1)).max() < 1e-5
        some = held.any(-1) & ~held.all(-1)
        assert some.any()
        assert (np.abs(y[some, 0] - 2.5) > 1e-3).all()
        # LongCat's router, the fields' defaults, renormalises nothing
        plain, _ = _rank_part(probe, rank, x, scoring="softmax",
                              renormalize=False)
        soft = np.asarray(jax.nn.softmax(x.reshape(-1, d) @ router))
        top = np.argsort(-soft, axis=-1)[:, :TOPK]
        in_rank = (top >= 4 * rank) & (top < 4 * rank + 4)
        assert np.abs(plain[:, 0] - 2.5 * (np.take_along_axis(
            soft, top, -1) * in_rank).sum(-1)).max() < 1e-5


# -- served by the engine --------------------------------------------------

def _serve(engine, prompts, max_new):
    reqs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    engine.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return reqs


def test_served_by_the_engine_with_counters_as_the_reference_counts(rank1):
    """Through ``ServingEngine`` with the defaults ``scripts/serve.py``
    uses: bucketed prefills, decode rounds with rows at different
    depths and retired rows, slots reused by shorter requests after
    longer ones, rings unfilled, exactly full and wrapped five times.
    Every served token's logit lies within LOGIT_TOL of the reference's
    best at its position (the benchmark's own check), and the
    device-side counters, published to the registry, equal the
    reference's own counts over exactly the tokens fed: its routing, and
    the pairs inside its masks. (Equal to the unit: float32 on both
    sides; a pick flips only on a tie at 1e-7.)"""
    cfg, model, params = rank1
    obs.reset_registry()
    engine = ServingEngine(model, params, max_slots=3, max_seq_len=64,
                           block_size=16, max_queue=64,
                           max_prefills_per_round=2)
    assert engine.prefix_cache is None and engine._store is None
    prompts = [_tokens(37, 50), _tokens(2, 51), _tokens(8, 52),
               _tokens(20, 53), _tokens(5, 54), _tokens(3, 55)]
    max_new = [3, 1, 9, 20, 14, 4]    # 40, 3, 17, 40, 19, 7 positions
    reqs = _serve(engine, prompts, max_new)
    engine.publish_device_counters()

    seqs = [(np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)]),
             len(p) - 1) for p, r in zip(prompts, reqs)]
    want, picks = ref.forward(cfg, SEED, seqs)
    for w, r in zip(want, reqs):
        served = np.asarray(r.tokens)
        gap = w.max(axis=-1) - w[np.arange(len(served)), served]
        assert gap.max() < LOGIT_TOL

    reg = obs.get_registry().snapshot()

    def read(name, kind, layer, attn=None):
        labels = f'kind="{kind}",layer="{layer}"' \
            + (f',attn="{attn}"' if attn else "")
        return reg.get(f"{name}{{{labels}}}", 0.0)

    rounds = len(engine.round_seconds)
    fed = sum(len(s[0]) - len(p) for s, p in zip(seqs, prompts))
    for layer, (_, window, dense) in enumerate(ref.layer_kinds(cfg)):
        kind = "window" if window else "full"
        # the pairs inside the reference's own mask: a prompt's, and
        # what decode adds to them
        pre = sum(ref.attended_counts(cfg, len(p))[layer] for p in prompts)
        both = sum(ref.attended_counts(cfg, len(s[0]))[layer] for s in seqs)
        assert read("attn_rows_attended_total", "prefill", layer, kind) \
            == pre
        assert read("attn_rows_attended_total", "decode", layer, kind) \
            == both - pre
        # a decode round scores the ring, or the row's whole length
        assert read("attn_rows_read_total", "decode", layer, kind) \
            == fed * (WINDOW if window else 64)
        if not window:
            # a full layer's prefill reads the key tiles its real
            # queries' tiles visit: at these sizes the bucket's one
            from pytorch_distributed_nn_tpu.serve.engine import _bucket_len
            assert read("attn_rows_read_total", "prefill", layer, kind) \
                == sum(len(p) * _bucket_len(len(p)) for p in prompts)
        if dense:
            assert read("moe_calls_total", "decode", layer) == 0
            continue
        sparse = layer - 1
        pk_pre = [pk[sparse, :len(p)] for pk, p in zip(picks, prompts)]
        pk_dec = np.concatenate([pk[sparse, len(p):]
                                 for pk, p in zip(picks, prompts)])
        held = lambda x: (x >= 4) & (x < 8)  # noqa: E731 - rank 1 of 4
        assert read("moe_calls_total", "prefill", layer) == len(prompts)
        assert read("moe_calls_total", "decode", layer) == rounds
        assert read("moe_picks_total", "prefill", layer) \
            == sum(x.size for x in pk_pre)
        assert read("moe_picks_total", "decode", layer) == pk_dec.size
        assert read("moe_held_pairs_total", "prefill", layer) \
            == sum(held(x).sum() for x in pk_pre)
        assert read("moe_held_pairs_total", "decode", layer) \
            == held(pk_dec).sum()
        assert read("moe_held_experts_touched_total", "prefill", layer) \
            == sum(len(np.unique(x[held(x)])) for x in pk_pre)
    # of all cache rows a decode round scores, the rings' share
    ring = sum(read("attn_rows_read_total", "decode", i, "window")
               for i in range(LAYERS))
    full = sum(read("attn_rows_read_total", "decode", i, "full")
               for i in range(LAYERS))
    assert ring / (ring + full) == pytest.approx(
        6 * WINDOW / (6 * WINDOW + 2 * 64))


def test_a_ring_model_gets_no_prefix_cache_and_says_so_once(rank1, caplog):
    """Built with ``scripts/serve.py``'s defaults (``prefix_cache=True``)
    the ring model's engine has no prefix cache and no store, says why
    in one log line, and refuses block export and ingest; a Llama-shaped
    model's engine keeps both."""
    _, model, params = rank1
    with caplog.at_level("INFO", logger=engine_mod.log.name):
        engine = ServingEngine(model, params, max_slots=2, max_seq_len=32)
    said = [r for r in caplog.records
            if "not rows by position" in r.getMessage()]
    assert len(said) == 1 and "no prefix cache" in said[0].getMessage()
    assert "12 ring" in said[0].getMessage()
    assert engine.prefix_cache is None and engine._store is None
    assert engine.scheduler.prefix_cache is None
    with pytest.raises(ValueError, match="12 ring"):
        engine.export_blocks([0])
    with pytest.raises(ValueError, match="12 ring"):
        engine.ingest_blocks(np.arange(16), None)

    mc = ModelConfig(name="llama3_8b", dtype="float32",
                     compute_dtype="float32")
    mc.extra = dict(vocab_size=VOCAB, num_layers=1, d_model=32, num_heads=4,
                    num_kv_heads=2, mlp_dim=64)
    llama = get_model(mc)
    lp = llama.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    plain = ServingEngine(llama, lp, max_slots=2, max_seq_len=32)
    assert plain.prefix_cache is not None and plain._store is not None
    assert not plain._not_by_position


# -- the other families' programs ------------------------------------------

@pytest.mark.parametrize("program", ["prefill", "step"])
@pytest.mark.parametrize("family", ["mistral", "longcat"])
def test_other_families_serve_programs_are_the_parents(family, program):
    """The window, the ring, the q/k norm and the query blocks are
    fields of ``MultiHeadAttention`` and the router's three differences
    fields of ``HeldExpertsMoE``; at their defaults a Mistral-shaped and
    a LongCat-shaped model's serve programs lower to the text they
    lowered to before those fields were written
    (``tests/serve_program_digests.py`` says how the file was made)."""
    import hashlib

    pinned = json.loads((ROOT / "tests" / "data"
                         / "serve_program_digests.json").read_text())
    text = serve_program_digests.lowered(family, program)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == pinned[f"{family}.{program}"]
    assert "sigmoid" not in text and "logistic" not in text
