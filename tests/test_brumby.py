"""Brumby's language model against its plain reference, on the CPU.

The program (``models/brumby.py``: pre-norm blocks whose mixer is
``nn/retention.py``'s gated power retention, served from a matrix-valued
state; an untied head) against ``benchmark/configs/brumby_14b_ref.py``
(float32, the ``a_ts`` form: scores squared under a decay mask,
normalised; it builds no state), at a small size that keeps the shape
(4 query heads over 2 key-value heads of 16: a state of 192 x 16 a
head, two diagonal tiles). Logits are compared, never sampled tokens.

The program's initialisers draw the gate's projection as any kernel, so
a gate is about 1/2, as under the benchmark's generator, and a state
forgets in a few positions: that would hide a fault in how a state is
carried over a length. So the three forms (chunks, a position a call,
the reference's scores) are held together at the mixer's level with
gates drawn near 1 (0.9975: a state still holds a third of its first
position after 440), and once with the harness's draw.
"""

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
from pytorch_distributed_nn_tpu.nn import retention  # noqa: E402
from pytorch_distributed_nn_tpu.ops.pallas import retention as kernel  # noqa: E402
from pytorch_distributed_nn_tpu.serve import ServingEngine  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

# (the package's ``generate`` is the function; this is its module)
gen = importlib.import_module(
    "pytorch_distributed_nn_tpu.inference.generate")
ref = common.load_module(
    ROOT / "benchmark" / "configs" / "brumby_14b_ref.py",
    "brumby_14b_ref_for_tests")

SEED = 2**31 + 50
VOCAB, LAYERS, D_MODEL, HEADS, KV, HD, MLP = 256, 2, 64, 4, 2, 16, 128
D = kernel.state_rows(HD)
# float32 on both sides, but another formulation of the same quotient:
# the reference sums (q . k)^2 over the keys, the program contracts phi(q)
# with a state built a chunk (or a position) at a time, which loses
# digits where a query is nearly orthogonal to the few keys its state
# still holds (MIX_TOL below has the arithmetic). Logits are of size up
# to ~4 and move by 6e-6 in the median and 5e-5 on the worst of 300
# positions. A wrong term (a state or a normaliser not carried, a padded
# position let into the state) moves them by 0.3 or more
# (test_a_fault_in_how_the_state_is_carried_...).
LOGIT_TOL = 2e-4
# the mixer's output alone, values of size ~1 (a convex mix of values).
# With gates near 1 a numerator and its normaliser are sums over hundreds
# of keys and the forms agree to 1e-5. Under the harness's draw a gate is
# about 1/2 and now and then near 0: a position may then see little but
# its own key, and where its query is nearly orthogonal to that key the
# recurrent form's (q . k)^2, a contraction of phi(q) with the state over
# 192 signed terms, loses digits to cancellation that the reference's
# square of one dot product keeps; numerator and normaliser are both
# tiny there and their quotient reads 1e-4 off.
MIX_TOL = {"near_one": 5e-5, "harness": 1e-3}


def _cfg(dtype: str = "float32") -> dict:
    """The reference's configuration at the small size."""
    return dict(
        hidden_size=D_MODEL, num_attention_heads=HEADS,
        num_key_value_heads=KV, head_dim=HD, intermediate_size=MLP,
        num_hidden_layers=LAYERS, rms_norm_eps=1e-6, vocab_size=VOCAB,
        torch_dtype=dtype, rope_theta=10000.0)


def _model(dtype: str = "float32", **over):
    """The program's model through its registry, shrunk by ``extra``."""
    mc = ModelConfig(name="brumby", dtype=dtype, compute_dtype=dtype)
    mc.extra = dict(dict(
        vocab_size=VOCAB, num_layers=LAYERS, d_model=D_MODEL,
        num_heads=HEADS, num_kv_heads=KV, head_dim=HD, mlp_dim=MLP,
        rope_theta=10000.0, norm_eps=1e-6), **over)
    return get_model(mc)


def _init(model):
    """The program's own initialisers' draw (logits of size ~1)."""
    return jax.jit(lambda: model.init(
        jax.random.key(SEED & 0x7FFFFFFF), jnp.zeros((1, 1), jnp.int32),
        train=False)["params"])()


def _ref_logits(cfg, params, seqs, quantize=None):
    """The reference on the program's weights, by name."""
    flat = weights.named_leaves(params)
    top = {k: v for k, v in flat.items() if not k.startswith("layer")}
    sub = lambda i: {k[len(f"layer{i}/"):]: v for k, v in flat.items()  # noqa: E731
                     if k.startswith(f"layer{i}/")}
    return ref.forward(cfg, top, sub, seqs, quantize)


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


# -- phi and its layout ------------------------------------------------------

@pytest.mark.parametrize("hd", [8, 16, 128])
def test_phi_is_the_symmetric_square_in_whole_tiles(hd):
    """``phi(a) . phi(b) = (a . b)^2``; the layout holds every distinct
    monomial, ``hd (hd + 1) / 2`` of them, and only the pairs below the
    diagonal of a diagonal tile twice; at a head of 128 that is 8,704
    rows for 8,256 monomials, whole tiles of 8 (the benchmark's cost
    functions count the same rows)."""
    from benchmark.lib import costs_brumby

    a, b = jax.random.normal(jax.random.key(hd), (2, 3, hd))
    got = (retention.phi(a) * retention.phi(b)).sum(-1)
    assert np.allclose(got, (a * b).sum(-1) ** 2, rtol=1e-5)
    lay = kernel.layout(hd)
    pairs = {(min(i, j), max(i, j)) for i, j in zip(lay["i"], lay["j"])}
    assert len(pairs) == hd * (hd + 1) // 2 == costs_brumby.monomials(hd)
    assert lay["D"] == costs_brumby.state_rows(hd) \
        == len(pairs) + (hd // 8) * 28
    assert lay["D"] % 8 == 0 and all(o % 8 == 0 for o in lay["offsets"])
    if hd == 128:
        assert (lay["D"], len(pairs)) == (8704, 8256)
        assert lay["D"] <= 1.1 * len(pairs)


# -- the three forms ---------------------------------------------------------

def _mixer(gates: str):
    """A :class:`PowerRetention` of the small size with its weights, and
    an input whose gates are near 1 (``near_one``: feature 0 is 6 and
    the gate's projection reads that feature alone, so every gate is
    sigmoid(6) = 0.9975) or as the harness draws them (``harness``: the
    projection a kernel, a gate about 1/2)."""
    mix = retention.PowerRetention(num_heads=HEADS, num_kv_heads=KV,
                                   head_dim=HD, rope_theta=10000.0)
    u = jax.random.normal(jax.random.key(3), (1, 440, D_MODEL))
    params = mix.init(jax.random.key(4), u[:, :1])["params"]
    if gates == "near_one":
        u = u.at[..., 0].set(6.0)
        params["gate"]["kernel"] = jnp.zeros((D_MODEL, KV)).at[0].set(1.0)
    for name in ("q_norm", "k_norm"):
        params[name]["scale"] = 1.0 + 0.1 * jax.random.normal(
            jax.random.key(5), (HD,))
    return mix, params, u


def _ref_mixer(params, u):
    flat = {"ret/" + k: v for k, v in weights.named_leaves(params).items()}
    return ref.retention(u[0], flat, 10000.0, 1e-6)


@pytest.mark.parametrize("gates", ["near_one", "harness"])
@pytest.mark.parametrize("chunk", [16, 128])
def test_chunks_steps_and_scores_are_one_mixer(gates, chunk, monkeypatch):
    """440 positions (27.5 chunks of 16: a ragged last one; 3.4 of 128)
    through the chunked form from an empty cache, through the step a
    position a call with the leaves handed from call to call, and
    through the reference's scores: the same outputs, and the two served
    forms leave the same state and normaliser. With gates near 1 the
    state at the end still holds its first positions (its norm is tens
    of times a single position's)."""
    monkeypatch.setattr(retention, "CHUNK", chunk)
    mix, params, u = _mixer(gates)
    T = u.shape[1]
    want = np.asarray(_ref_mixer(params, u))
    pos = jnp.arange(T)[None]
    empty = mix.init(jax.random.key(0), u, decode=True)["cache"]
    run = jax.jit(lambda c, x, p: mix.apply(
        {"params": params, "cache": c}, x, decode=True, positions=p,
        mutable=["cache"]))
    got, after = run(empty, u, pos)
    assert np.abs(np.asarray(got[0]) - want).max() < MIX_TOL[gates]
    cache, rows = empty, []
    for t in range(T):
        y, m = run(cache, u[:, t:t + 1], pos[:, t:t + 1])
        cache = m["cache"]
        rows.append(np.asarray(y[0, 0]))
    assert np.abs(np.stack(rows) - want).max() < MIX_TOL[gates]
    for leaf in ("ret_state", "ret_norm"):
        a, b = np.asarray(after["cache"][leaf]), np.asarray(cache[leaf])
        assert np.abs(a - b).max() < 1e-4 * np.abs(a).max(), leaf
    if gates == "near_one":
        one = run(empty, u[:, :1], pos[:, :1])[1]["cache"]["ret_state"]
        assert np.linalg.norm(after["cache"]["ret_state"]) \
            > 10 * np.linalg.norm(one)


def test_full_forward_logits_match_reference(served):
    """300 positions through the uncached forward (2.3 chunks of 128,
    from zeros, nothing kept)."""
    cfg, model, params = served
    toks = _tokens(300, 1)
    got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(toks)[None])[0]
    want = _ref_logits(cfg, params, [(toks, 0)])[0]
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL


# -- through the cache, as the engine's programs apply the model -------------

_prefill = jax.jit(engine_mod._apply_prefill_at, static_argnums=(0,))


def _state_map(cache, leaf: str, f):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: f(x) if getattr(path[-1], "key", "") == leaf else x,
        cache)


def _prefill_then_decode(model, params, toks, prompt_len, pad, real=None,
                         between=None):
    """A padded prefill of ``toks[:prompt_len]`` into a fresh cache of one
    row, then the rest a token a round. Returns the logits of every
    position from the prompt's last on. ``real``: how many of the fed
    positions the prefill is told are real (default ``prompt_len``);
    ``between`` changes the cache after the prefill (the faults)."""
    cache = gen.init_cache(model, 1, 512)
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
    """A prompt of 203 tokens padded to 256 (two chunks), then 40 decode
    rounds through the cache, against the reference's full forward over
    all 243: logits, in float32 (LOGIT_TOL and its reason above)."""
    cfg, model, params = served
    toks = _tokens(243, 2)
    got = _prefill_then_decode(model, params, toks, 203, 256)
    want = _ref_logits(cfg, params, [(toks, 202)])[0]
    assert got.shape == want.shape == (41, VOCAB)
    assert np.abs(got - want).max() < LOGIT_TOL


@pytest.mark.parametrize("fault", ["state_not_carried", "norm_not_carried",
                                   "padding_advances"])
def test_a_fault_in_how_the_state_is_carried_fails_the_tolerance(
        served, fault):
    """What LOGIT_TOL is held against, in the rounds after the prefill:
    the state or the normaliser zeroed at the hand-over, the bucket's
    padded positions let through to both. Each moves a logit by 0.3 or
    more, under the program's own draw of the gates (about 1/2)."""
    cfg, model, params = served
    toks = _tokens(215, 3)
    want = _ref_logits(cfg, params, [(toks, 202)])[0]
    got = _prefill_then_decode(
        model, params, toks, 203, 256,
        real=256 if fault == "padding_advances" else None,
        between={"state_not_carried": lambda c: _state_map(
            c, "ret_state", jnp.zeros_like),
            "norm_not_carried": lambda c: _state_map(
                c, "ret_norm", jnp.zeros_like)}.get(fault))
    # (the prefill's own row is the prompt's last and is sound)
    assert np.abs(got[1:] - want[1:]).max() > 0.3


def _state_leaves(cache):
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", "") in ("ret_state", "ret_norm")}


def _prefill_row(model, params, fed, n):
    """The cache of one row after a prefill of ``fed``, whose first ``n``
    tokens are real."""
    _, cache = _prefill(
        model, params, gen.init_cache(model, 1, 512),
        jnp.asarray(fed)[None], jnp.asarray([n], jnp.int32),
        jnp.zeros((1,), jnp.int32))
    return cache


def test_padding_and_inactive_rows_leave_both_leaves_bit_for_bit(served):
    """A prompt of 203 real tokens in a bucket of 256 leaves every state
    leaf the same bit for bit whatever the 53 padded positions hold, and
    as the 203 fed alone leave it (to 1e-5 of its size: another program,
    the same sums). A decode round leaves an inactive row's leaves as
    they were, while the active row's move."""
    _, model, params = served
    toks = _tokens(256, 4)
    alone = _prefill_row(model, params, toks[:203], 203)
    padded = _prefill_row(model, params, toks, 203)
    other = _prefill_row(
        model, params, np.concatenate([toks[:203], _tokens(53, 6)]), 203)
    a, p, o = (_state_leaves(x) for x in (alone, padded, other))
    assert len(a) == 2 * LAYERS
    assert all(np.abs(v).max() > 0 for v in a.values())
    for name in a:
        assert np.array_equal(p[name], o[name]), name
        assert np.abs(a[name] - p[name]).max() \
            < 1e-5 * np.abs(a[name]).max(), name

    # two rows in one batch cache: row 0 active, row 1 not
    batch = gen.init_cache(model, 2, 512)
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
    any other leaf: a slot taken again starts from what the insert wrote
    and nothing of its last tenant."""
    _, model, params = served
    dirty = jax.tree.map(lambda x: jnp.full_like(x, 7),
                         gen.init_cache(model, 3, 512))
    row = _prefill_row(model, params, _tokens(256, 5), 203)
    out = engine_mod._insert_row(dirty, row, 1,
                                 totals=model.device_counter_leaf)
    want, got = _state_leaves(row), _state_leaves(out)
    for name in want:
        assert np.array_equal(got[name][1], want[name][0]), name
        assert (got[name][0] == 7).all() and (got[name][2] == 7).all()


# -- the kernels against their oracles ---------------------------------------

def _step_operands(B: int = 3, G: int = 2):
    ks = jax.random.split(jax.random.key(11), 6)
    S = jax.random.normal(ks[0], (B, KV, D, HD))
    g = jax.nn.sigmoid(jax.random.normal(ks[1], (B, KV)) + 2.0)
    k, v = jax.random.normal(ks[2], (2, B, KV, HD))
    q = jax.random.normal(ks[3], (B, KV, G, HD))
    return S, g, k, v, q


@pytest.mark.parametrize("active", [(1, 1, 1), (0, 1, 0), (1, 0, 1),
                                    (0, 0, 1), (0, 0, 0)])
def test_the_step_kernel_is_its_oracle_and_moves_no_idle_row(active):
    """``retention_step`` in interpret mode against ``step_xla``: the
    active rows' outputs and states (float32, the same products; the
    sum over a slab's rows in another order: 1e-5 on values of size ~10),
    and an idle row's state bit for bit what went in, wherever the idle
    rows lie (before, between, after the active ones, or all)."""
    S, g, k, v, q = _step_operands()
    act = jnp.asarray(active, bool)
    # what the mixer does for a row that is not active: gate 1, key 0
    g1 = jnp.where(act[:, None], g, 1.0)
    k0 = jnp.where(act[:, None, None], k, 0.0)
    want_y, want_S = retention.step_xla(S, g1, k0, v, q)
    got_y, got_S = kernel.step(S + 0, g1, k0, v, q, act, interpret=True)
    for b, on in enumerate(active):
        if on:
            assert np.abs(np.asarray(got_y - want_y))[b].max() < 1e-4
            assert np.abs(np.asarray(got_S - want_S))[b].max() < 1e-5
            assert not np.array_equal(np.asarray(got_S[b]),
                                      np.asarray(S[b]))
        else:
            assert np.array_equal(np.asarray(got_S[b]), np.asarray(S[b]))
            assert not np.asarray(got_y[b]).any()
    assert np.array_equal(np.asarray(want_S)[~np.asarray(act)],
                          np.asarray(S)[~np.asarray(act)])


@pytest.mark.parametrize("n", [1, 3])
def test_the_chunk_kernel_is_its_oracle(n):
    """``retention_chunk`` in interpret mode against ``chunk_xla`` over
    ``n`` chunks of 16 from a state that is not empty: ``phi(Q) S``
    against the state before each chunk, and the state after the last
    (the kernel takes a slab in a window of ``head_dim`` rows whose
    leading rows meet coefficients of 0: the same sums)."""
    B, G, C = 2, 2, 16
    ks = jax.random.split(jax.random.key(12), 6)
    S = jax.random.normal(ks[0], (B, KV, D, HD))
    q = jax.random.normal(ks[1], (B, KV, n, G, C, HD))
    k, vd = jax.random.normal(ks[2], (2, B, KV, n, C, HD))
    g = jax.nn.sigmoid(jax.random.normal(ks[3], (B, KV, n)))
    want_p, want_S = retention.chunk_xla(S, q, k, vd, g)
    got_p, got_S = kernel.chunk(S + 0, q, k, vd, g, interpret=True)
    assert np.abs(np.asarray(got_p - want_p)).max() \
        < 1e-5 * np.abs(np.asarray(want_p)).max()
    assert np.abs(np.asarray(got_S - want_S)).max() < 1e-4


@pytest.mark.parametrize("T", [1, 40])
def test_the_mixer_on_the_kernels_is_the_mixer_in_jax_numpy(T):
    """:func:`power_retention` with the kernels (interpret mode) against
    its ``jax.numpy`` execution, rows of unequal real length in one
    call: outputs at the real positions, both leaves after."""
    B, G = 2, 2
    ks = jax.random.split(jax.random.key(13), 8)
    S = jax.random.normal(ks[0], (B, KV, D, HD))
    z = jnp.abs(jax.random.normal(ks[1], (B, KV, D)))
    q = jax.random.normal(ks[2], (B, T, KV, G, HD))
    k, v = jax.random.normal(ks[3], (2, B, T, KV, HD))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, T, KV)) + 2.0)
    real = jnp.arange(T)[None] < jnp.asarray([[T], [T * 5 // 8]])
    want = retention.power_retention(S, z, q, k, v, log_g, real, chunk=16,
                                     on_core=False)
    got = retention.power_retention(S + 0, z, q, k, v, log_g, real,
                                    chunk=16, on_core=True, interpret=True)
    mask = np.asarray(real)[..., None, None, None]
    assert np.abs(np.where(mask, np.asarray(got[0] - want[0]), 0)).max() \
        < 1e-4
    for a, b in zip(got[1:], want[1:]):
        assert np.abs(np.asarray(a - b)).max() < 1e-4


# -- served by the engine ----------------------------------------------------

def _serve_all(engine, prompts, max_new):
    reqs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    engine.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return reqs


def _engine(model, params, slots=2, **kw):
    return ServingEngine(model, params, max_slots=slots, max_seq_len=256,
                         block_size=16, max_queue=64,
                         max_prefills_per_round=2, **kw)


def test_served_through_dirty_slots_as_served_alone_with_counters(
        served, caplog):
    """Through ``ServingEngine`` with the defaults ``scripts/serve.py``
    uses (``prefix_cache=True``): two slots, five requests of different
    lengths, so rows of unequal length share a batch and the later ones
    are admitted rounds apart into slots retired requests left dirty.
    Each request's tokens are those it gets served alone (an engine of
    one slot, a request at a time), and every served token's logit lies
    within LOGIT_TOL of the reference's best at its position. The engine
    has no prefix cache and no store, says why once, and refuses block
    export and ingest. The device-side counters, published to the
    registry, count the real tokens fed: ``retention_tokens_total`` of
    kind prefill is the prompts' tokens (not their buckets'), of kind
    decode the tokens the rounds were fed (not the slots). All of the
    cache is state: ``serve_cache_bytes`` reads 0 by position."""
    cfg, model, params = served
    obs.reset_registry()
    with caplog.at_level("INFO", logger=engine_mod.log.name):
        engine = _engine(model, params)
    said = [r.getMessage() for r in caplog.records
            if "not rows by position" in r.getMessage()]
    assert len(said) == 1 and "no prefix cache" in said[0]
    assert f"{2 * LAYERS} recurrent state" in said[0]
    assert engine.prefix_cache is None and engine._store is None
    with pytest.raises(ValueError, match="recurrent state"):
        engine.export_blocks([0])

    prompts = [_tokens(137, 50), _tokens(5, 51), _tokens(41, 52),
               _tokens(150, 53), _tokens(9, 54)]   # buckets 256, 16, 64
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

    def read(name, kind, layer):
        return reg.get(f'{name}{{kind="{kind}",layer="{layer}"}}', 0.0)

    rounds = len(engine.round_seconds)
    fed = sum(len(s[0]) - len(p) for s, p in zip(seqs, prompts))
    for layer in range(LAYERS):
        assert read("retention_calls_total", "prefill", layer) \
            == len(prompts)
        assert read("retention_tokens_total", "prefill", layer) \
            == sum(len(p) for p in prompts)
        assert read("retention_calls_total", "decode", layer) == rounds
        assert read("retention_tokens_total", "decode", layer) == fed
    state = 2 * LAYERS * KV * (D * HD + D) * 4      # slots, layers, heads
    assert reg['serve_cache_bytes{leaves="not_by_position"}'] == state
    assert reg['serve_cache_bytes{leaves="by_position"}'] == 0
    lowered = {k for k in reg if k.startswith("retention_programs_total")}
    assert any("step, jax.numpy" in k for k in lowered)
    assert any("chunks of" in k for k in lowered)


def test_the_registered_model_is_the_published_configuration():
    """With no override the registry builds the sizes of
    ``benchmark/configs/brumby_14b.json`` (but its depth, which the file
    cuts), every layer's two leaves are declared state, and the
    reference's spec is the program's tree."""
    cfg = common.load_json(
        ROOT / "benchmark" / "configs" / "brumby_14b.json")
    model = get_model(ModelConfig(name="brumby"))
    assert (model.vocab_size, model.d_model, model.num_heads,
            model.num_kv_heads, model.head_dim, model.mlp_dim,
            model.rope_theta, model.norm_eps) == (
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["intermediate_size"], cfg["rope_theta"], cfg["rms_norm_eps"])
    assert model.num_layers == cfg["reduced_from"]["num_hidden_layers"] == 40
    assert cfg["num_hidden_layers"] == 8
    (what, paths), = model.leaves_not_by_position().items()
    assert "recurrent state" in what and len(paths) == 2 * 40
    names = model.device_counter_names()
    assert len(names) == 2 * 40 * 2
    assert names[0] == ("retention_calls_total",
                        {"kind": "prefill", "layer": "0"})
    small, small_cfg = _model(), _cfg()
    shapes = jax.eval_shape(lambda: small.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    spec = ref.param_spec(small_cfg)
    want = {n: tuple(s) for n, s in spec["top"]}
    for i in range(LAYERS):
        want.update({f"layer{i}/{n}": tuple(s) for n, s in spec["layer"]})
    assert {n: tuple(x.shape) for n, x in
            weights.named_leaves(shapes).items()} == want
