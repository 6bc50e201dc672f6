"""Jamba's language model against its plain reference, on the CPU.

The program (``models/jamba.py``: pre-norm blocks whose mixer is
``nn/mamba.py``'s Mamba-1 layer or, every second layer here, attention
without any position signal; a tied head) against
``benchmark/configs/jamba2_3b_ref.py`` (float32, the recurrence one
position a step, no cache), at a small size that keeps the pattern (two
periods, an attention layer after a Mamba layer in each, ``d_state`` 16,
``d_conv`` 4).
Logits are compared, never sampled tokens.

The weights are drawn by the program's own initialisers, which are
Mamba's published ones (``nn/mamba.py``: ``softplus`` of the step's bias
log-uniform in 1e-3 .. 0.1, ``A = -(1 .. 16)``), so that a state carries
hundreds of positions: under the benchmark's generator ``A`` is about -1
and the step about 0.8, a state that forgets in a few positions would
hide a fault in how it is carried. One test ties the reference to the
published code (``transformers``' ``JambaForCausalLM``, slow path).
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
from pytorch_distributed_nn_tpu.nn import mamba  # noqa: E402
from pytorch_distributed_nn_tpu.serve import ServingEngine  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

# (the package's ``generate`` is the function; this is its module)
gen = importlib.import_module(
    "pytorch_distributed_nn_tpu.inference.generate")
ref = common.load_module(
    ROOT / "benchmark" / "configs" / "jamba2_3b_ref.py",
    "jamba2_3b_ref_for_tests")

SEED = 2**31 + 40
VOCAB, LAYERS, PERIOD, OFFSET = 256, 4, 2, 1     # M A M A
D_MODEL, HEADS, MLP, RANK = 64, 4, 128, 8
# float32 on both sides, but another order of the same sums: the state
# laid out (16, d_inner) against (d_inner, 16), carried through the
# cache, the convolution's tail gathered, attention in tiles. Logits are
# of size up to ~6 (the embedding scaled for it under the tied head) and
# move by 6e-6 to 7e-6. A wrong term (a tail one position off, a padded
# position let into the state, a state not carried) moves them by 4 to 5
# (test_a_fault_in_how_the_state_is_carried_...).
LOGIT_TOL = 5e-5
# bf16 weights and activations with the float32 state, against the
# float32 reference on the same (bf16-rounded) weights, logits up to 5.0:
# 2^-9 a rounding, some thirty roundings deep, reads 1.35e-2 in the mean
# and 0.104 on the worst of 10,000 logits. The state's precision cannot
# be seen through that: a state leaf rounded to float16 or to bf16 at
# every call reads 1.32e-2 and 1.39e-2. So the state is held to the bf16
# program's own uncached forward, which rounds in the same places (both
# traced with one position an iteration, so that prefill, round and
# forward run one step's code and agree to 2.5e-8 in the mean; sixteen
# positions fused into an iteration round a sum otherwise now and then,
# and read 2.0e-3): against that, 1.03e-2 with a float16 leaf and 1.29e-2
# with a bf16 one (any change of 1e-4 in a state moves roundings
# everywhere after it).
BF16_TOL, BF16_WORST = 3e-2, 0.3
BF16_STATE_TOL = 1e-3


def _cfg(dtype: str = "float32") -> dict:
    """The reference's configuration at the small size."""
    return dict(
        hidden_size=D_MODEL, num_attention_heads=HEADS,
        num_key_value_heads=1, intermediate_size=MLP,
        num_hidden_layers=LAYERS, attn_layer_period=PERIOD,
        attn_layer_offset=OFFSET, mamba_expand=2, mamba_d_state=16,
        mamba_d_conv=4, mamba_dt_rank=RANK, num_experts=1,
        rms_norm_eps=1e-6, vocab_size=VOCAB, torch_dtype=dtype,
        rope_theta=None)


def _model(dtype: str = "float32", **over):
    """The program's model through its registry, shrunk by ``extra``
    (``rope_theta`` as the harness passes it: dropped)."""
    mc = ModelConfig(name="jamba", dtype=dtype, compute_dtype=dtype)
    mc.extra = dict(dict(
        vocab_size=VOCAB, num_layers=LAYERS, d_model=D_MODEL,
        num_heads=HEADS, num_kv_heads=1, mlp_dim=MLP,
        attn_layer_period=PERIOD, attn_layer_offset=OFFSET,
        mamba_dt_rank=RANK, rope_theta=None,
        norm_eps=1e-6), **over)
    return get_model(mc)


def _init(model):
    """The program's own initialisers' draw; the embedding scaled so that
    the tied head's logits are of size ~1."""
    params = jax.jit(lambda: model.init(
        jax.random.key(SEED & 0x7FFFFFFF), jnp.zeros((1, 1), jnp.int32),
        train=False)["params"])()
    params["tok_embed"]["embedding"] = \
        params["tok_embed"]["embedding"] * (10.0 / D_MODEL ** 0.5)
    return params


def _ref_logits(cfg, params, seqs, quantize=None):
    """The reference on the program's weights, by name."""
    flat = weights.named_leaves(params)
    top = {k: v for k, v in flat.items() if not k.startswith("layer")}
    return ref.forward(
        cfg, top, lambda i: ref._sub(flat, f"layer{i}"), seqs, quantize)


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


def test_the_spec_is_the_programs_tree_and_the_draw_is_in_mambas_ranges(
        served):
    """The benchmark's generator gives the program's layout leaf for
    leaf (26 Mamba layers as ``layer<i>``, the attention layers as
    ``attn<j>`` among the top leaves); and the initialisers' draw has a
    step in 1e-3 .. 0.1 and ``A = -(1 .. 16)``, a state that still holds
    a twentieth of an input after 200 positions."""
    cfg, model, params = served
    weights.check_layout(jax.eval_shape(
        lambda: weights.tree(SEED, ref.param_spec(cfg))), params)
    assert [n for n, a in model._layers()] == \
        ["layer0", "attn0", "layer1", "attn1"]
    m = params["layer0"]["mamba"]
    dt = np.asarray(jax.nn.softplus(m["dt_proj"]["bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert np.allclose(-np.exp(np.asarray(m["A_log"])),
                       -np.arange(1, 17)[None], rtol=1e-6)
    # the slowest mode of the median channel after 200 positions
    assert np.exp(-np.median(dt) * 200) > 0.05


def test_full_forward_logits_match_reference(served):
    """300 positions through the uncached forward: 37 iterations of 8
    positions and a ragged last one."""
    cfg, model, params = served
    toks = _tokens(300, 1)
    got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(toks)[None])[0]
    want = _ref_logits(cfg, params, [(toks, 0)])[0]
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL


_prefill = jax.jit(engine_mod._apply_prefill_at, static_argnums=(0,))


def _state_map(cache, leaf: str, f):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: f(x) if getattr(path[-1], "key", "") == leaf else x,
        cache)


def _prefill_then_decode(model, params, toks, prompt_len, pad, max_len,
                         real=None, between=None, each_round=None):
    """A padded prefill of ``toks[:prompt_len]`` into a fresh cache of one
    row, then the rest a token a round, as the engine's programs apply
    the model. Returns the logits of every position from the prompt's
    last on. ``real``: how many of the fed positions the prefill is told
    are real (default ``prompt_len``); ``between`` and ``each_round``
    change the cache after the prefill and after every round (the
    faults)."""
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
        if each_round is not None:
            cache = each_round(cache)
        rows.append(np.asarray(logits[0]))
    return np.stack(rows)


def test_padded_prefill_then_decode_matches_the_full_forward(served):
    """A prompt of 203 tokens padded to 256, then 60 decode rounds
    through the cache, against the reference's full forward over all 263:
    logits, in float32 (LOGIT_TOL and its reason above)."""
    cfg, model, params = served
    toks = _tokens(263, 2)
    got = _prefill_then_decode(model, params, toks, 203, 256, 288)
    want = _ref_logits(cfg, params, [(toks, 202)])[0]
    assert got.shape == want.shape == (61, VOCAB)
    assert np.abs(got - want).max() < LOGIT_TOL


@pytest.mark.parametrize("fault", ["tail_one_off", "padding_advances",
                                   "state_not_carried"])
def test_a_fault_in_how_the_state_is_carried_fails_the_tolerance(
        served, fault):
    """What LOGIT_TOL is held against, in the rounds after the prefill:
    the convolution's tail one position off, the bucket's padded
    positions let through to the state and the tail, the state not
    carried from the prefill. Each moves a logit by 4 or more, where the
    assertion asks for two hundred tolerances."""
    cfg, model, params = served
    toks = _tokens(263, 2)
    want = _ref_logits(cfg, params, [(toks, 202)])[0]
    kw = {
        "tail_one_off": dict(between=lambda c: _state_map(
            c, "conv_tail", lambda x: jnp.roll(x, 1, axis=1))),
        "padding_advances": dict(real=256),
        "state_not_carried": dict(between=lambda c: _state_map(
            c, "ssm_state", jnp.zeros_like)),
    }[fault]
    got = _prefill_then_decode(model, params, toks, 203, 256, 288, **kw)
    assert np.abs(got[1:] - want[1:]).max() > 200 * LOGIT_TOL


@pytest.fixture(scope="module")
def served_bf16(served):
    """(model, params, tokens, the program's own uncached forward from
    the prompt's last position on, the same through the cache): bf16
    weights (the float32 draw, rounded) and activations, a prompt of 203
    padded to 256 and 40 rounds. Both bf16 programs are traced here with
    the recurrence one position an iteration (BF16_STATE_TOL's reason,
    above); no other test runs them."""
    model = _model("bfloat16")
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), served[2])
    toks = _tokens(243, 3)
    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision("highest"):
        patch.setattr(mamba, "selective_scan", functools.partial(
            mamba.selective_scan, unroll=1))
        own = np.asarray(jax.jit(
            lambda p, t: model.apply({"params": p}, t))(
                params, jnp.asarray(toks)[None])[0])[202:]
        cached = _prefill_then_decode(model, params, toks, 203, 256, 256)
    return model, params, toks, own, cached


def test_bf16_holds_its_two_tolerances(served_bf16):
    """The served precision: bf16 weights and activations, the state in
    float32. Against the float32 reference on the same weights it holds
    BF16_TOL on the mean and BF16_WORST on the worst logit, which is the
    rounding of every activation and says nothing of the state. Against
    the program's own uncached forward in bf16, which rounds in the same
    places, it holds BF16_STATE_TOL, thirty times tighter."""
    _, params, toks, own, got = served_bf16
    assert np.abs(got - own).mean() < BF16_STATE_TOL
    want = _ref_logits(_cfg("bfloat16"), params, [(toks, 202)])[0]
    assert np.abs(want).max() > 3.0
    assert np.abs(got - want).mean() < BF16_TOL
    assert np.abs(got - want).max() < BF16_WORST


@pytest.mark.parametrize("state", ["float16", "bfloat16"])
def test_a_half_precision_state_fails_the_tight_tolerance(served_bf16,
                                                           state):
    """With the state leaf rounded to float16 or bf16 after every call,
    as a cache leaf of that type would hold it, the bf16 program fails
    BF16_STATE_TOL by ten times (asked here: five)."""
    model, params, toks, own, _ = served_bf16
    rounded = jax.jit(lambda c: _state_map(
        c, "ssm_state", lambda x: x.astype(state).astype(jnp.float32)))
    got = _prefill_then_decode(model, params, toks, 203, 256, 256,
                               between=rounded, each_round=rounded)
    assert np.abs(got - own).mean() > 5 * BF16_STATE_TOL


def _scan_inputs(T: int, B: int = 2, D: int = 32, N: int = 16):
    ks = jax.random.split(jax.random.key(7), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, D)) - 3.0)
    c = jax.random.normal(ks[1], (B, T, D))
    b = jax.random.normal(ks[2], (B, T, N))
    c_out = jax.random.normal(ks[3], (B, T, N))
    a = -jnp.exp(jax.random.normal(ks[4], (N, D)))
    h = jax.random.normal(ks[5], (B, N, D))
    return h, dt, c, b, c_out, a


def test_a_position_an_iteration_is_the_recurrence_by_hand():
    """From a carried-in state that is not zero, the scan a position an
    iteration equals the recurrence a position a call (``T = 1``, the
    decode round's form, the state handed from call to call) and the
    definition worked in float64 (float32, the same products in the same
    order: 1e-5 on values of size ~3)."""
    h, dt, c, b, c_out, a = _scan_inputs(21)
    y1, h1 = mamba.selective_scan(h, dt, c, b, c_out, a, unroll=1)
    one, ys, hs = jax.jit(mamba.selective_scan), [], h
    for t in range(21):
        y, hs = one(hs, *(x[:, t:t + 1] for x in (dt, c, b, c_out)), a)
        ys.append(y)
    assert np.abs(np.asarray(jnp.concatenate(ys, 1) - y1)).max() < 1e-5
    assert np.abs(np.asarray(hs - h1)).max() < 1e-5
    assert np.abs(np.asarray(h1)).max() > 0.5
    hh = np.asarray(h, np.float64)
    for t in range(21):
        hh = np.exp(np.asarray(dt[:, t, None, :] * a, np.float64)) * hh \
            + np.asarray((dt[:, t] * c[:, t])[:, None, :]
                         * b[:, t, :, None], np.float64)
    assert np.abs(hh - np.asarray(h1)).max() < 1e-5


@pytest.mark.parametrize("T", [32, 21, 5])
def test_the_served_scan_is_a_position_an_iteration(T):
    """``SCAN_UNROLL`` positions an iteration, as every prefill runs,
    against one: over whole iterations, a ragged last one and a call
    shorter than an iteration, outputs and the carried-out state."""
    assert T % mamba.SCAN_UNROLL == 0 or T in (21, 5)
    h, dt, c, b, c_out, a = _scan_inputs(T)
    y1, h1 = mamba.selective_scan(h, dt, c, b, c_out, a, unroll=1)
    yc, hc = mamba.selective_scan(h, dt, c, b, c_out, a)
    assert np.abs(np.asarray(yc - y1)).max() < 1e-5
    assert np.abs(np.asarray(hc - h1)).max() < 1e-5


def _state_leaves(cache):
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", "") in ("ssm_state", "conv_tail")}


def _prefill_row(model, params, fed, n, max_len=288):
    """The cache of one row after a prefill of ``fed``, whose first ``n``
    tokens are real."""
    _, cache = _prefill(
        model, params, gen.init_cache(model, 1, max_len),
        jnp.asarray(fed)[None], jnp.asarray([n], jnp.int32),
        jnp.zeros((1,), jnp.int32))
    return cache


def test_padding_and_inactive_rows_leave_both_leaves_bit_for_bit(served):
    """A step of 0 holds a state bit for bit, in one position and in
    five. A prompt of 203 real tokens in a bucket of 256 leaves every
    state leaf the same bit for bit whatever the 53 padded positions hold,
    and as the 203 fed alone leave it (to 1e-5: another program, the same
    sums; the first layer's tail is the last three *real* inputs,
    exactly). A decode round leaves an inactive row's leaves as they
    were, while the active row's move."""
    _, model, params = served
    h, dt, c, b, c_out, a = _scan_inputs(5)
    for T in (1, 5):
        held = mamba.selective_scan(h, 0 * dt[:, :T], c[:, :T], b[:, :T],
                                    c_out[:, :T], a)[1]
        assert np.array_equal(np.asarray(held), np.asarray(h))
    toks = _tokens(256, 4)
    alone = _prefill_row(model, params, toks[:203], 203)
    padded = _prefill_row(model, params, toks, 203)
    other = _prefill_row(
        model, params, np.concatenate([toks[:203], _tokens(53, 6)]), 203)
    a, p, o = (_state_leaves(x) for x in (alone, padded, other))
    assert len(a) == 2 * 2 and all(np.abs(v).max() > 0 for v in a.values())
    for name in a:
        assert np.array_equal(p[name], o[name]), name
        assert np.abs(a[name] - p[name]).max() < 1e-5, name
    # the first layer's inputs are the embedding's rows: exactly
    assert np.array_equal(a["layer0/mamba/conv_tail"],
                          p["layer0/mamba/conv_tail"])

    # two rows in one batch cache: row 0 active, row 1 not
    batch = gen.init_cache(model, 2, 288)
    for slot in range(2):
        batch = engine_mod._insert_row(batch, padded, slot,
                                       totals=model.device_counter_leaf)
    before = _state_leaves(batch)
    _, after = gen.decode_step_ragged(
        model, params, batch, jnp.asarray(toks[203:205]),
        jnp.asarray([203, 203], jnp.int32),
        token_mask=jnp.asarray([[True], [False]]))
    after = _state_leaves(after)
    for name in before:
        assert np.array_equal(before[name][1], after[name][1]), name
        assert not np.array_equal(before[name][0], after[name][0]), name


def test_insert_row_overwrites_the_whole_state_of_a_dirty_slot(served):
    """``_insert_row`` copies a state leaf ``(1, ...)`` over a slot's like
    any other leaf: nothing of the last occupant is left."""
    _, model, params = served
    dirty = jax.tree.map(lambda x: jnp.full_like(x, 7),
                         gen.init_cache(model, 3, 288))
    row = _prefill_row(model, params, _tokens(256, 5), 203)
    out = engine_mod._insert_row(dirty, row, 1,
                                 totals=model.device_counter_leaf)
    want, got = _state_leaves(row), _state_leaves(out)
    for name in want:
        assert np.array_equal(got[name][1], want[name][0]), name
        assert (got[name][0] == 7).all() and (got[name][2] == 7).all()


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
    lengths, so the later ones are admitted rounds apart into slots
    retired requests left dirty. Each request's tokens are those it gets
    served alone (an engine of one slot, a request at a time), and every
    served token's logit lies
    within LOGIT_TOL of the reference's best at its position. The engine
    has no prefix cache and no store, says why once, and refuses block
    export and ingest. The device-side counters, published to the
    registry, count the real tokens fed: ``ssm_tokens_total`` of kind
    prefill is the prompts' tokens (not their buckets'), of kind decode
    the tokens the rounds were fed; the attention layers count the rows
    inside their masks."""
    cfg, model, params = served
    obs.reset_registry()
    with caplog.at_level("INFO", logger=engine_mod.log.name):
        engine = _engine(model, params)
    said = [r.getMessage() for r in caplog.records
            if "not rows by position" in r.getMessage()]
    assert len(said) == 1 and "no prefix cache" in said[0]
    assert "4 recurrent state" in said[0]
    assert engine.prefix_cache is None and engine._store is None
    with pytest.raises(ValueError, match="4 recurrent state"):
        engine.export_blocks([0])
    with pytest.raises(ValueError, match="4 recurrent state"):
        engine.ingest_blocks(np.arange(16), None)

    prompts = [_tokens(37, 50), _tokens(5, 51), _tokens(41, 52),
               _tokens(50, 53), _tokens(9, 54)]     # buckets 64 and 16
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
    for layer, (_, attention) in enumerate(ref.layer_kinds(cfg)):
        if attention:
            pre = sum(len(p) * (len(p) + 1) // 2 for p in prompts)
            both = sum(len(s[0]) * (len(s[0]) + 1) // 2 for s in seqs)
            assert read("attn_rows_attended_total", "prefill", layer,
                        "full") == pre
            assert read("attn_rows_attended_total", "decode", layer,
                        "full") == both - pre
            assert read("attn_rows_read_total", "decode", layer,
                        "full") == fed * 128
            assert read("ssm_calls_total", "decode", layer) == 0
            continue
        assert read("ssm_calls_total", "prefill", layer) == len(prompts)
        assert read("ssm_tokens_total", "prefill", layer) \
            == sum(len(p) for p in prompts)
        assert read("ssm_calls_total", "decode", layer) == rounds
        assert read("ssm_tokens_total", "decode", layer) == fed
    # the two gauges: what of the batch cache is state, what rows
    state = 2 * 2 * (16 * 128 * 4 + 3 * 128 * 4)    # slots, layers, leaves
    rows = 2 * 2 * 2 * 128 * 16 * 4                 # slots, layers, k and v
    assert reg['serve_cache_bytes{leaves="not_by_position"}'] == state
    assert reg['serve_cache_bytes{leaves="by_position"}'] == rows


def test_the_registered_model_is_the_published_configuration():
    """With no override the registry builds the sizes of
    ``benchmark/configs/jamba2_3b.json``: attention at layers 7 and 21,
    the 26 Mamba layers named ``layer0 .. layer25`` in model order for
    the harness's generator, and the reference reads the same order."""
    cfg = common.load_json(ROOT / "benchmark" / "configs" / "jamba2_3b.json")
    model = get_model(ModelConfig(name="jamba"))
    for field, key in (
            ("vocab_size", "vocab_size"), ("num_layers", "num_hidden_layers"),
            ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"),
            ("mlp_dim", "intermediate_size"),
            ("attn_layer_period", "attn_layer_period"),
            ("attn_layer_offset", "attn_layer_offset"),
            ("mamba_expand", "mamba_expand"),
            ("mamba_d_state", "mamba_d_state"),
            ("mamba_d_conv", "mamba_d_conv"),
            ("mamba_dt_rank", "mamba_dt_rank"),
            ("norm_eps", "rms_norm_eps")):
        assert getattr(model, field) == cfg[key], field
    layers = model._layers()
    assert [i for i, (_, attention) in enumerate(layers) if attention] \
        == [7, 21]
    assert [n for n, attention in layers if not attention] \
        == [f"layer{i}" for i in range(26)]
    assert [tuple(x) for x in ref.layer_kinds(cfg)] == list(layers)


# -- the reference against the published code ------------------------------

def test_reference_is_transformers_jamba_on_the_slow_path():
    """``JambaForCausalLM`` with ``use_mamba_kernels=False`` (``slow_forward``)
    given the reference's weights yields the reference's logits, in
    float32, at a tiny size that keeps the pattern (3e-4 on logits of
    size up to ~20 under the generator's N(0, 1) embedding, three times
    what two float32 implementations, torch's and XLA's, read: 1e-4)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.models.jamba import JambaConfig, JambaForCausalLM

    cfg = dict(_cfg(), vocab_size=96)
    hf = JambaForCausalLM(JambaConfig(
        vocab_size=96, hidden_size=D_MODEL, intermediate_size=MLP,
        num_hidden_layers=LAYERS, num_attention_heads=HEADS,
        num_key_value_heads=1, attn_layer_period=PERIOD,
        attn_layer_offset=OFFSET, num_experts=1, num_experts_per_tok=1,
        expert_layer_period=2, expert_layer_offset=1, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=RANK,
        mamba_conv_bias=True, mamba_proj_bias=False, rms_norm_eps=1e-6,
        tie_word_embeddings=True, use_mamba_kernels=False,
        pad_token_id=0, attn_implementation="eager")).float().eval()
    spec = ref.param_spec(cfg)
    top = weights.top(SEED, spec)
    t = lambda x: torch.tensor(np.asarray(x, np.float32))  # noqa: E731
    state = {"model.embed_tokens.weight": t(top["tok_embed/embedding"]),
             "model.final_layernorm.weight": t(top["final_norm/scale"])}
    mamba_layers = []
    for i, (name, attention) in enumerate(ref.layer_kinds(cfg)):
        if attention:
            w = ref._sub(top, name)
        else:
            w = weights.layer(SEED, spec, len(mamba_layers))
            mamba_layers.append(w)
        pre = f"model.layers.{i}."
        state[pre + "input_layernorm.weight"] = t(w["input_norm/scale"])
        state[pre + "pre_ff_layernorm.weight"] = t(w["pre_ff_norm/scale"])
        for proj in ("gate_proj", "up_proj", "down_proj"):
            state[pre + f"feed_forward.{proj}.weight"] = \
                t(w[f"mlp/{proj}/kernel"]).T
        if attention:
            for ours, theirs in (("query", "q_proj"), ("key", "k_proj"),
                                 ("value", "v_proj")):
                k = np.asarray(w[f"attn/{ours}/kernel"], np.float32)
                state[pre + f"self_attn.{theirs}.weight"] = \
                    t(k.reshape(D_MODEL, -1)).T
            state[pre + "self_attn.o_proj.weight"] = t(np.asarray(
                w["attn/out/kernel"], np.float32).reshape(-1, D_MODEL)).T
            continue
        m = pre + "mamba."
        for ours, theirs in (("in_proj", "in_proj"), ("x_proj", "x_proj"),
                             ("dt_proj", "dt_proj"), ("out_proj", "out_proj")):
            state[m + f"{theirs}.weight"] = t(w[f"mamba/{ours}/kernel"]).T
        state[m + "dt_proj.bias"] = t(w["mamba/dt_proj/bias"])
        state[m + "conv1d.weight"] = t(w["mamba/conv1d/kernel"]).T[:, None, :]
        state[m + "conv1d.bias"] = t(w["mamba/conv1d/bias"])
        state[m + "A_log"] = t(w["mamba/A_log"])
        state[m + "D"] = t(w["mamba/D"])
        for ours, theirs in (("dt_norm", "dt_layernorm"),
                             ("b_norm", "b_layernorm"),
                             ("c_norm", "c_layernorm")):
            state[m + f"{theirs}.weight"] = t(w[f"mamba/{ours}/scale"])
    missing, unexpected = hf.load_state_dict(state, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}
    hf.tie_weights()
    toks = np.random.default_rng([SEED, 9]).integers(
        0, 96, size=(45,)).astype(np.int64)
    with torch.no_grad():
        theirs = hf(torch.tensor(toks)[None], use_cache=False,
                    num_logits_to_keep=0).logits[0].numpy()
    ours = ref.forward(cfg, top, mamba_layers.__getitem__,
                       [(toks.astype(np.int32), 0)])[0]
    assert np.abs(ours).max() > 10.0
    assert np.abs(ours - theirs).max() < 3e-4
    del transformers
