"""The per-row cache write (``_row_update``) against a plain loop, and
int8 KV-cache decode (nn/attention.py cache_dtype="int8").

Three layers of oracle:
1. the scale-folding identity — int8-cache attention must equal the
   dequantize-then-float-attend reference almost exactly (both see the
   SAME quantization error, so the comparison isolates the folded-scale
   implementation);
2. whole-model decode vs the float cache — greedy generations from a
   small Llama must agree token-for-token at moderate lengths (the
   quantization error is real here, so the oracle is behavioral);
3. structure — cache leaves are int8 + f32 scales, ~half the bytes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.inference import generate
from pytorch_distributed_nn_tpu.inference.generate import init_cache
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.nn.attention import (
    _cache_attention,
    _quantize_kv,
    _row_update,
    dot_product_attention,
)


def _small_extra(cache_dtype="compute"):
    return dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                mlp_dim=128, vocab_size=97, cache_dtype=cache_dtype)


def _write_rows_one_by_one(buf, new, starts, drop_out_of_range):
    """The reference: one ``dynamic_update_slice`` a row, which clamps
    the window onto the row's end; or no write at all for a row whose
    start is out of range."""
    S = buf.shape[1]
    for i, s in enumerate(np.asarray(starts).tolist()):
        if drop_out_of_range and not 0 <= s < S:
            continue
        row = jax.lax.dynamic_update_slice(
            buf[i], new[i], (s,) + (0,) * (buf.ndim - 2))
        buf = buf.at[i].set(row)
    return buf


# a leaf's shape past (B, S): K/V heads, MLA's latent, the int8 scales
_LEAVES = {"kv": (2, 4), "latent": (6,), "scales": (2,)}
_ROW = 8   # positions a row
# per-row starts for B = 3; "dead" holds a stopped row at max_seq_len
# and one further out, beside a live row
_STARTS = {"zero": [0, 0, 0], "ragged": [5, 0, 3],
           "last": [_ROW - 1] * 3, "dead": [_ROW, 2, _ROW + 5]}


@pytest.mark.parametrize("starts", list(_STARTS))
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("leaf,dtype", [
    ("kv", jnp.bfloat16), ("kv", jnp.int8), ("latent", jnp.bfloat16),
    ("scales", jnp.float32)])
def test_row_update_matches_per_row_writes(leaf, dtype, T, B, starts):
    """Under ``jit`` with the buffer donated, every leaf shape that goes
    through ``_row_update``: a live row gets the bytes a per-row
    ``dynamic_update_slice`` gives it and nothing else moves. The stated
    rule for a row out of range: one token a row is not written (a
    scatter drops it), several are clamped onto the row's end as a
    whole (a prefill's window)."""
    rng = np.random.RandomState(7)

    def draw(shape):
        x = rng.randint(-100, 100, shape) if dtype == jnp.int8 \
            else rng.randn(*shape)
        return jnp.asarray(x).astype(dtype)

    buf = draw((B, _ROW) + _LEAVES[leaf])
    new = draw((B, T) + _LEAVES[leaf])
    at = jnp.asarray(_STARTS[starts][:B], jnp.int32)
    if starts != "dead":
        at = jnp.minimum(at, _ROW - T)   # a live window lies in the row
    want = _write_rows_one_by_one(buf, new, at, drop_out_of_range=T == 1)
    got = jax.jit(_row_update, donate_argnums=0)(buf + 0, new, at)
    assert got.dtype == buf.dtype and got.shape == buf.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_row_update_ring_rows_past_one_wrap():
    """The ring's call: positions past one wrap land in row
    ``position mod R``, each slot at its own."""
    rng = np.random.RandomState(8)
    B, R = 3, 4
    ring = jnp.asarray(rng.randn(B, R, 2, 4), jnp.float32)
    k = jnp.asarray(rng.randn(B, 1, 2, 4), jnp.float32)
    positions = jnp.asarray([R + 1, 3 * R, 2 * R - 1], jnp.int32)
    got = np.asarray(jax.jit(_row_update, donate_argnums=0)(
        ring + 0, k, positions % R))
    want = np.asarray(ring).copy()
    for i, row in enumerate([1, 0, R - 1]):
        want[i, row] = np.asarray(k)[i, 0]
    np.testing.assert_array_equal(got, want)


def test_quantize_kv_roundtrip():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 5, 3, 8).astype(np.float32)) * 3.0
    q, s = _quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 5, 3)
    deq = q.astype(jnp.float32) * s[..., None]
    # symmetric per-row absmax: error bounded by scale/2 per element
    err = np.abs(np.asarray(deq - x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    assert (err <= bound).all()
    # zero rows round-trip exactly with scale 1
    qz, sz = _quantize_kv(jnp.zeros((1, 2, 2, 4)))
    assert np.all(np.asarray(sz) == 1.0) and np.all(np.asarray(qz) == 0)


@pytest.mark.parametrize("gqa", [1, 2])
def test_folded_scale_identity_vs_dequantized_reference(gqa):
    """int8-cache attention == float attention over the dequantized
    cache (same quantization error on both sides — this isolates the
    scale-folding algebra)."""
    rng = np.random.RandomState(1)
    B, T, S, Hkv, D = 2, 3, 16, 2, 16
    H = Hkv * gqa
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32)) * 2
    v = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32))
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    # positions 0..T-1 query a cache filled to S (arbitrary valid mask)
    pos = jnp.arange(T)[None] + (S - T)
    pos_mask = jnp.arange(S)[None, None, :] <= pos[:, :, None]

    got = _cache_attention(q, kq, vq, pos_mask, jnp.float32,
                           kscale=ks, vscale=vs)
    k_deq = kq.astype(jnp.float32) * ks[..., None]
    v_deq = vq.astype(jnp.float32) * vs[..., None]
    want = dot_product_attention(q, k_deq, v_deq, causal=False,
                                 impl="xla", mask=pos_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_cache_structure_and_size():
    B, L = 2, 32
    ref = get_model(ModelConfig(name="llama3_8b",
                                extra=_small_extra("compute")))
    int8 = get_model(ModelConfig(name="llama3_8b",
                                 extra=_small_extra("int8")))
    c_ref = init_cache(ref, B, L)
    c_int8 = init_cache(int8, B, L)
    payload = [x for x in jax.tree.leaves(c_int8) if x.ndim == 4]
    scales = [x for x in jax.tree.leaves(c_int8) if x.ndim == 3]
    assert all(x.dtype == jnp.int8 for x in payload)
    assert all(x.dtype == jnp.float32 for x in scales)
    bytes_ref = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(c_ref))
    bytes_int8 = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(c_int8))
    # bf16 payload -> int8 + f32/D scales: ~0.56x at D=16, and strictly
    # half the payload bytes at the real D=128
    assert bytes_int8 < 0.75 * bytes_ref


def test_unknown_cache_dtype_raises():
    model = get_model(ModelConfig(name="llama3_8b",
                                  extra=_small_extra("fp4")))
    with pytest.raises(ValueError, match="cache_dtype"):
        model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                   train=False, decode=True)


def test_decode_matches_float_cache_tokens():
    """Greedy generation with the int8 cache agrees with the float
    cache token-for-token on a small model (behavioral oracle — real
    quantization error, must not flip decisions on a well-separated
    argmax)."""
    extra_f = _small_extra("compute")
    extra_q = _small_extra("int8")
    ref = get_model(ModelConfig(name="llama3_8b", extra=extra_f))
    got = get_model(ModelConfig(name="llama3_8b", extra=extra_q))
    rng = jax.random.key(3)
    prompt = jax.random.randint(rng, (2, 12), 0, 97, jnp.int32)
    params = ref.init(jax.random.key(0), prompt[:, :1],
                      train=False)["params"]
    out_ref = np.asarray(generate(ref, params, prompt, 24))
    out_q = np.asarray(generate(got, params, prompt, 24))
    agree = (out_ref == out_q).mean()
    assert agree == 1.0, f"token agreement {agree:.3f}\n{out_ref}\n{out_q}"


def test_decode_matches_full_context_logits():
    """int8-cache decode logits stay close to the no-cache full-context
    forward (the same oracle test_generate.py runs for the float
    cache, with tolerance for int8 cache error)."""
    model = get_model(ModelConfig(name="llama3_8b",
                                  extra=_small_extra("int8")))
    rng = jax.random.key(5)
    toks = jax.random.randint(rng, (2, 10), 0, 97, jnp.int32)
    params = model.init(jax.random.key(0), toks[:, :1],
                        train=False)["params"]
    full = model.apply({"params": params}, toks, train=False)
    cache = init_cache(model, 2, 10)
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, toks, train=False,
        decode=True, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=0.1, atol=0.05)


def _attention_module():
    from pytorch_distributed_nn_tpu.nn.attention import MultiHeadAttention

    return MultiHeadAttention(num_heads=4, head_dim=8, num_kv_heads=2,
                              causal=True, rotary=True, use_bias=False)


_ROWS = 640   # 512 fed tokens against them are scores worth tiling


def _filled_cache(attn, x, fill):
    """Parameters, and a (B, _ROWS) cache with ``fill`` tokens a row
    fed."""
    B, d = x.shape[0], x.shape[-1]
    variables = attn.init(jax.random.key(0), jnp.zeros((B, _ROWS, d)),
                          decode=True)
    _, mutated = attn.apply(variables, x[:, :fill], decode=True,
                            mutable=["cache"])
    return variables["params"], mutated["cache"]


@pytest.mark.parametrize("mode", ["per_row", "shared_index"])
def test_prefill_behind_filled_rows_is_the_dense_masked_softmax(
        monkeypatch, mode):
    """Several tokens a row (512: grouped-query, rotary, a nonzero
    start: 100 rows filled, in per-row mode the second row rewinds to
    37) through the blockwise routine against the dense masked softmax
    over the whole row cache, which is what ran here before: 1e-5 in
    float32."""
    from pytorch_distributed_nn_tpu.nn import attention

    attn = _attention_module()
    x = jax.random.normal(jax.random.key(1), (2, 612, 32))
    params, cache = _filled_cache(attn, x, 100)
    assert attention.prefill_in_tiles(512, _ROWS)
    kw = dict(cache_positions=jnp.asarray([100, 37])) \
        if mode == "per_row" else {}
    entered = []
    blockwise = attention._prefill_attention
    monkeypatch.setattr(
        attention, "_prefill_attention",
        lambda *a: entered.append(1) or blockwise(*a))

    def prefill():
        return attn.apply({"params": params, "cache": cache}, x[:, 100:],
                          decode=True, mutable=["cache"], **kw)

    def dense(q, k, v, positions, lengths=None):
        seen = jnp.arange(k.shape[1])[None, None, :] \
            <= positions[:, :, None]
        return _cache_attention(q, k, v, seen, q.dtype)

    got, got_cache = prefill()
    assert entered == [1]
    monkeypatch.setattr(attention, "_prefill_attention", dense)
    want, want_cache = prefill()
    assert float(jnp.abs(want).mean()) > 0.05
    assert float(jnp.abs(got - want).max()) < 1e-5
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_one_token_a_row_off_the_chip_lowers_to_the_dense_routine(
        monkeypatch):
    """A decode round (T = 1) off a TPU is the dense routine over the
    flat row cache reshaped by head: its lowered text is pinned (sha256
    taken at ISSUE 53, which laid the rows flat and meant to change it;
    it depends on the installed JAX, as
    ``tests/data/serve_program_digests.json`` does), the prefill's
    routine is never entered and the round's kernel is not called."""
    import hashlib

    from pytorch_distributed_nn_tpu.nn import attention

    def never(*a, **k):
        raise AssertionError("a decode round off the chip left the dense "
                             "routine")

    attn = _attention_module()
    x = jax.random.normal(jax.random.key(1), (2, 12, 32))
    variables = attn.init(jax.random.key(0), jnp.zeros((2, 24, 32)),
                          decode=True)
    params, cache = variables["params"], variables["cache"]
    assert cache["cached_key"].shape == (2, 24, 2 * 8)
    monkeypatch.setattr(attention, "_prefill_attention", never)
    monkeypatch.setattr(attention, "round_attention", never)
    with jax.default_matmul_precision(None):
        text = jax.jit(lambda p, c, x, at: attn.apply(
            {"params": p, "cache": c}, x, decode=True, mutable=["cache"],
            cache_positions=at)).lower(
                params, cache, x[:, 5:6], jnp.asarray([5, 2])).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _ONE_TOKEN_TEXT, \
        hashlib.sha256(text.encode()).hexdigest()


_ONE_TOKEN_TEXT = (
    "deaedc903781d676dc1e22a273db631b8ee9319871cf5aa52d1f7d20f15fe17e")


# -- a decode round through the kernel, served ------------------------------

# small models of the three families whose decode round changed routine,
# (registry name, ``extra``). Mistral-shaped: grouped queries over flat
# rows. LFM2-shaped: heads of 64, two a lane tile, beside convolution
# state. K-EXAONE-shaped: full layers beside rings.
_SERVED_SHAPES = {
    "mistral": ("llama3_8b", dict(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        mlp_dim=128, vocab_size=97, rope_theta=1e6)),
    "lfm2": ("lfm2_8b_a1b", dict(
        vocab_size=97, num_layers=4, d_model=256, num_heads=4,
        num_kv_heads=2, mlp_dim=128, expert_mlp_dim=32, num_experts=4,
        moe_topk=2, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv", "full_attention"))),
    "k_exaone": ("k_exaone", dict(
        vocab_size=97, num_layers=4, d_model=64, num_heads=8,
        num_kv_heads=2, head_dim=16, mlp_dim=96, window=8,
        expert_mlp_dim=32, num_experts=4, moe_topk=2)),
}


@pytest.mark.parametrize("family", list(_SERVED_SHAPES))
def test_served_through_the_rounds_kernel_as_generated_alone(
        monkeypatch, family):
    """Greedy decode through ``ServingEngine`` with every decode round's
    attention over rows by position going through the kernel (told it
    is the routine, interpret mode, key blocks of 16) is token for
    token the sequential ``generate`` of each prompt alone through the
    dense routine: seven requests over three slots, so slots retire and
    are refilled mid-run, a refilled slot's stale rows lie past its new
    depth, and a slot that is idle for a round reads nothing and its
    zeros reach nobody."""
    from pytorch_distributed_nn_tpu.nn import attention
    from pytorch_distributed_nn_tpu.ops.pallas import prefix_attention as pa
    from pytorch_distributed_nn_tpu.serve import ServingEngine

    name, extra = _SERVED_SHAPES[family]
    model = get_model(ModelConfig(name=name, dtype="float32",
                                  compute_dtype="float32", extra=extra))
    params = model.init(jax.random.key(2), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 97, size=(n,)).astype(np.int32)
               for n in (5, 11, 3, 17, 8, 2, 9)]
    budgets = [7, 3, 9, 5, 12, 4, 6]
    with jax.default_matmul_precision("highest"):
        alone = [np.asarray(generate(model, params, p[None], n))[0, len(p):]
                 for p, n in zip(prompts, budgets)]
        rounds = []
        kernel = pa.round_attention

        def counted(*a, **kw):
            rounds.append(a[0].shape)
            return kernel(*a, **kw, interpret=True)

        monkeypatch.setattr(attention, "round_key_block",
                            lambda S, heads, d, dtype: 16 if S % 16 == 0 else 0)
        monkeypatch.setattr(attention, "round_attention", counted)
        jax.clear_caches()      # the programs traced with the dense routine
        try:
            engine = ServingEngine(model, params, max_slots=3,
                                   max_seq_len=64, block_size=8,
                                   max_queue=16, max_prefills_per_round=2)
            reqs = [engine.submit(p, n) for p, n in zip(prompts, budgets)]
            engine.run_until_idle()
        finally:
            jax.clear_caches()  # nor these kept for the next test
    assert rounds and all(shape[0] == 3 for shape in rounds)
    for want, r in zip(alone, reqs):
        assert r.state == "done"
        np.testing.assert_array_equal(np.asarray(r.tokens), want)
