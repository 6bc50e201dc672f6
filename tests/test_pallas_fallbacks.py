"""CPU-side tests of the Pallas kernel wrappers: the jnp fallbacks must be
exact, and callers must integrate with impl='flash' transparently. The
kernels themselves are validated on the real chip (bench + tests/tpu/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.nn.attention import dot_product_attention
from pytorch_distributed_nn_tpu.ops.pallas.flash_attention import (
    flash_attention,
)
from pytorch_distributed_nn_tpu.ops.pallas.quantize import (
    dequantize_int8,
    quantize_int8,
)


def _qkv(hkv=8):
    rng = np.random.RandomState(0)
    q = rng.randn(2, 32, 8, 16).astype(np.float32)
    k = rng.randn(2, 32, hkv, 16).astype(np.float32)
    v = rng.randn(2, 32, hkv, 16).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_impl_matches_xla(causal):
    q, k, v = _qkv()
    want = np.asarray(dot_product_attention(q, k, v, causal=causal,
                                            impl="xla"))
    got = np.asarray(dot_product_attention(q, k, v, causal=causal,
                                           impl="flash"))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)


def test_flash_impl_gqa_native():
    # grouped kv goes straight into flash_attention (no expansion at the
    # caller — the kernel maps each Q head onto its group's KV rows)
    q, k, v = _qkv(hkv=2)
    want = np.asarray(dot_product_attention(q, k, v, causal=True,
                                            impl="xla"))
    got = np.asarray(dot_product_attention(q, k, v, causal=True,
                                           impl="flash"))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)


def test_flash_gqa_grads_match_xla():
    # dk/dv must come back GROUPED (shape of the unexpanded kv) and
    # equal the head-group sum the expanded path would produce
    q, k, v = _qkv(hkv=2)
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)

    def loss(impl):
        def f(q, k, v):
            out = dot_product_attention(q, k, v, causal=True, impl=impl)
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    got = loss("flash")
    want = loss("xla")
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_rejects_mask():
    q, k, v = _qkv()
    mask = np.ones((2, 32), bool)
    with pytest.raises(ValueError):
        dot_product_attention(q, k, v, causal=False, impl="flash",
                              mask=mask)


def test_flash_raw_rejects_indivisible_heads():
    q, k, v = _qkv(hkv=3)  # 8 q heads % 3 kv heads != 0
    with pytest.raises(ValueError):
        flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))


def test_int8_quantize_roundtrip_unbiased():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 1024).astype(np.float32)
    scale = np.abs(x).max() / 127.0
    # average many stochastic roundings → unbiased estimate of x
    acc = np.zeros_like(x)
    n = 50
    for seed in range(n):
        q = quantize_int8(jnp.asarray(x), scale, seed=seed)
        acc += np.asarray(dequantize_int8(q, scale))
    np.testing.assert_allclose(acc / n, x, atol=3 * scale)


def test_int8_bucket_reduce_close(mesh8):
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_nn_tpu.ops.buckets import make_bucket_reduce

    rng = np.random.RandomState(1)
    grads = {"w": rng.randn(8, 64).astype(np.float32)}
    reduce_fn = make_bucket_reduce(bucket_mb=1.0, quantized="int8")
    mapped = jax.shard_map(reduce_fn, mesh=mesh8,
                           in_specs=P("data"), out_specs=P("data"),
                           check_vma=False)
    got = np.asarray(jax.jit(mapped)(grads)["w"])
    want = np.broadcast_to(grads["w"].mean(0, keepdims=True), (8, 64))
    scale = np.abs(grads["w"]).max() / 127.0
    np.testing.assert_allclose(got, want, atol=2 * scale)


def test_bucket_reduce_bad_mode():
    from pytorch_distributed_nn_tpu.ops.buckets import make_bucket_reduce

    with pytest.raises(ValueError):
        make_bucket_reduce(quantized="fp4")


def test_flash_blockwise_backward_matches_autodiff():
    """The hand-written blockwise flash backward must equal jax.grad of
    the dense reference (CPU, pure jnp)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_nn_tpu.ops.pallas.flash_attention import (
        _attention_reference,
        _flash_bwd_blockwise,
    )

    rng = np.random.RandomState(3)
    BH, T, D = 4, 256, 32
    q = jnp.asarray(rng.randn(BH, T, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(BH, T, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(BH, T, D), jnp.float32)
    g = jnp.asarray(rng.randn(BH, T, D), jnp.float32)

    for causal in (True, False):
        out, vjp = jax.vjp(
            lambda a, b, c: _attention_reference(a, b, c, causal=causal),
            q, k, v,
        )
        want_dq, want_dk, want_dv = vjp(g)
        got_dq, got_dk, got_dv = _flash_bwd_blockwise(
            q, k, v, out, g, causal=causal, block_q=64
        )
        for got, want, name in [(got_dq, want_dq, "dq"),
                                (got_dk, want_dk, "dk"),
                                (got_dv, want_dv, "dv")]:
            err = float(jnp.abs(got - want).max())
            assert err < 1e-4, (causal, name, err)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernels_grouped_stats_interpret(causal):
    """Interpret-mode regression for the sublane-grouped lse/delta
    blocking (_stat_subl): nq=12 gives subl=8 with a PARTIAL tail group
    (rows 8-11), plus GQA group addressing — the geometry where the
    qi % subl row store, the qi // subl group maps, and the causal
    clamp in stat_fix can all go wrong while every nq==1 test stays
    green (2026-08-01: the per-qi-row variant only failed on chip)."""
    from pytorch_distributed_nn_tpu.ops.pallas.flash_attention import (
        _attention_reference,
        _flash_bhtd,
        _flash_bwd_pallas,
    )

    rng = np.random.RandomState(7)
    BH, BKV, T, D, blk = 4, 2, 96, 16, 8  # nq = 12 -> subl = 8
    q = jnp.asarray(rng.randn(BH, T, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(BKV, T, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(BKV, T, D), jnp.float32)
    g = jnp.asarray(rng.randn(BH, T, D), jnp.float32)
    kx = jnp.repeat(k, BH // BKV, axis=0)
    vx = jnp.repeat(v, BH // BKV, axis=0)

    out, lse = _flash_bhtd(q, k, v, causal=causal, block_q=blk,
                           block_k=blk, interpret=True)
    ref_out, vjp = jax.vjp(
        lambda a, b, c: _attention_reference(a, b, c, causal=causal),
        q, kx, vx,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)
    # lse rows must land in the right (group, row) slots
    scale = D ** -0.5
    s = jnp.einsum("btd,bsd->bts", q, kx) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
    want_lse = jax.nn.logsumexp(s, axis=-1).reshape(BH, T // blk, blk)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)

    delta = jnp.sum(out.astype(jnp.float32) * g, -1).reshape(
        BH, T // blk, blk)
    dq, dk, dv = _flash_bwd_pallas(q, k, v, g, lse, delta, causal=causal,
                                   block_q=blk, block_k=blk,
                                   interpret=True)
    want_dq, want_dkx, want_dvx = vjp(g)
    want_dk = want_dkx.reshape(BKV, BH // BKV, T, D).sum(1)
    want_dv = want_dvx.reshape(BKV, BH // BKV, T, D).sum(1)
    for got, want, name in [(dq, want_dq, "dq"), (dk, want_dk, "dk"),
                            (dv, want_dv, "dv")]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_flash_rejects_cross_length():
    import jax.numpy as jnp
    import pytest

    from pytorch_distributed_nn_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    q = jnp.zeros((1, 128, 4, 32))
    kv = jnp.zeros((1, 64, 4, 32))
    with pytest.raises(ValueError, match="self-attention"):
        flash_attention(q, kv, kv, causal=False)


def test_auto_impl_occupancy_policy(monkeypatch):
    """The 'auto' flash-vs-xla switch (r3 occupancy policy): flash at
    T >= 2048, or at T >= 1024 with >= 64 B*H rows per chip — global
    trace shapes divided by device count so pod DP at per-chip batch 1
    stays on xla (the measured under-occupied regime)."""
    from pytorch_distributed_nn_tpu.nn import attention as att

    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(att.jax, "device_count", lambda: 1)

    def pick(B, T, H, S=None, devices=1, mask=False):
        monkeypatch.setattr(att.jax, "device_count", lambda: devices)
        return att._auto_impl((B, T, H, 64), (B, S or T, H, 64),
                              has_mask=mask)

    assert pick(1, 2048, 4) == "flash"      # length alone from 2k
    assert pick(1, 1024, 16) == "xla"       # 16 rows: under-occupied
    assert pick(4, 1024, 16) == "flash"     # 64 rows: break-even
    assert pick(16, 1024, 16) == "flash"
    assert pick(1, 512, 64) == "xla"        # never below 1k
    assert pick(8, 1024, 16, devices=8) == "xla"   # pod DP: 16/chip
    assert pick(8, 2048, 16, devices=8) == "flash"  # length still wins
    assert pick(4, 1024, 16, mask=True) == "xla"   # masks need xla
    assert pick(4, 1024, 16, S=512) == "xla"       # cross-length
    monkeypatch.setattr(att.jax, "default_backend", lambda: "cpu")
    assert pick(16, 4096, 16) == "xla"      # CPU always falls back
