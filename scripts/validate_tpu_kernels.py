#!/usr/bin/env python
"""Validate Pallas kernels against their jnp oracles on the real TPU chip
(tests/ runs on CPU where the wrappers fall back, so this script is the
kernels' correctness gate; run it whenever a kernel changes)."""

import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu.ops.pallas.flash_attention import (
    _attention_reference,
    flash_attention,
)
from pytorch_distributed_nn_tpu.ops.pallas.quantize import (
    dequantize_int8,
    quantize_int8,
)


def check_flash() -> bool:
    ok = True
    rng = np.random.RandomState(0)
    # (B, T, H, D, Hkv): last two cases exercise GQA-native KV streaming
    for (B, T, H, D, Hkv) in [(2, 512, 8, 128, 8), (1, 1024, 4, 64, 4),
                              (1, 1024, 8, 64, 2), (2, 512, 8, 128, 4)]:
        q = rng.randn(B, T, H, D).astype(np.float32) * 0.3
        k = rng.randn(B, T, Hkv, D).astype(np.float32) * 0.3
        v = rng.randn(B, T, Hkv, D).astype(np.float32)
        for causal in (True, False):
            got = np.asarray(flash_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                causal=causal))
            to_bh = lambda x: jnp.asarray(x).transpose(0, 2, 1, 3).reshape(
                B * H, T, D)  # noqa: E731
            expand = lambda x: jnp.repeat(  # noqa: E731
                jnp.asarray(x), H // Hkv, axis=2)
            want = np.asarray(_attention_reference(
                to_bh(q), to_bh(expand(k)), to_bh(expand(v)),
                causal=causal,
            )).reshape(B, H, T, D).transpose(0, 2, 1, 3)
            err = float(np.abs(got - want).max())
            line_ok = err < 2e-2
            ok &= line_ok
            print(f"flash B{B} T{T} H{H}/kv{Hkv} D{D} causal={causal}: "
                  f"max_err={err:.2e} {'OK' if line_ok else 'FAIL'}")
    return ok


def check_flash_grad() -> bool:
    """Gradients through the full custom_vjp path (Pallas forward + the
    Pallas two-pass lse-replay backward) vs autodiff of the dense
    reference. Shapes cover BOTH grid regimes: T=512 (single-block,
    nq=nk=1) and T=2048 (multi-block — the qi-indexed lse plane, the
    causal live/clamp index maps, and cross-block scratch accumulation
    only execute when nq, nk > 1, and that is the only regime 'auto'
    uses flash in). T=1152 forces block 128 (sole divisor), nq=9:
    the sublane-grouped lse/delta blocking (_stat_subl) gets a PARTIAL
    tail group (1 valid row of 8) — out-of-bounds stat blocks on dim -2
    only exist on the real chip, interpret mode can't catch them."""
    ok = True
    rng = np.random.RandomState(4)
    # Hkv < H covers the GQA backward: grouped dk/dv accumulated over
    # the head group inside the dkv kernel's inner grid dim
    for (B, T, H, D, Hkv) in [(2, 512, 4, 64, 4), (1, 2048, 4, 64, 4),
                              (1, 2048, 4, 64, 2), (1, 1152, 4, 64, 2)]:
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.3
        k = jnp.asarray(rng.randn(B, T, Hkv, D).astype(np.float32)) * 0.3
        v = jnp.asarray(rng.randn(B, T, Hkv, D).astype(np.float32))

        def to_bh(x):
            h = x.shape[2]
            return x.transpose(0, 2, 1, 3).reshape(B * h, T, D)

        for causal in (True, False):
            def f_flash(q, k, v):
                return (flash_attention(q, k, v, causal=causal)
                        .astype(jnp.float32).sum())

            def f_ref(q, k, v):
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
                return (_attention_reference(
                    to_bh(q), to_bh(k), to_bh(v), causal=causal,
                ).astype(jnp.float32).sum())

            got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
            want = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
            for gg, ww, name in zip(got, want, ("dq", "dk", "dv")):
                err = float(jnp.abs(gg - ww).max())
                line_ok = err < 2e-2
                ok &= line_ok
                print(f"flash-grad T{T} {name} causal={causal}: "
                      f"max_err={err:.2e} {'OK' if line_ok else 'FAIL'}")
    return ok


def check_quantize() -> bool:
    rng = np.random.RandomState(1)
    x = rng.randn(8, 1024).astype(np.float32)
    scale = float(np.abs(x).max() / 127.0)
    acc = np.zeros_like(x)
    n = 32
    for seed in range(n):
        q = quantize_int8(jnp.asarray(x), scale, seed=seed)
        acc += np.asarray(dequantize_int8(q, scale))
    err = float(np.abs(acc / n - x).max())
    ok = err < 4 * scale
    print(f"int8 stochastic quantize: mean-err={err:.2e} "
          f"(scale {scale:.2e}) {'OK' if ok else 'FAIL'}")
    return ok


def check_int8_matmul() -> bool:
    """The weight-only int8 dequant matmul (ops/pallas/int8_matmul.py)
    vs its dequantized-f32 oracle — the kernel under the TRUE-8B decode
    path — at llama layer shapes plus padded-tail geometries."""
    from pytorch_distributed_nn_tpu.ops.pallas.int8_matmul import (
        int8_matmul,
        quantize_weight,
    )

    rng = np.random.RandomState(3)
    ok = True
    for (m, k, n) in [(16, 4096, 14336), (16, 4096, 1024),
                      (1024, 4096, 4096), (5, 48, 200)]:
        w = jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.05)
        x = jnp.asarray(rng.randn(m, k).astype(np.float32))
        q, s = quantize_weight(w)
        got = int8_matmul(x, q, s, out_dtype=jnp.float32)[:, :n]
        ref = jnp.asarray(x, jnp.bfloat16).astype(jnp.float32) @ (
            q.astype(jnp.float32)[:k, :n] * s[:, :n])
        err = float(jnp.max(jnp.abs(got - ref))
                    / (float(jnp.max(jnp.abs(ref))) + 1e-9))
        line_ok = err < 2e-2
        ok &= line_ok
        print(f"int8-matmul ({m},{k},{n}): rel_err={err:.2e} "
              f"{'OK' if line_ok else 'FAIL'}")
    return ok


def check_ring_block() -> bool:
    """The fused ring-attention block kernel vs its jnp oracle: a chain of
    block updates with rotating offsets — exactly what one device runs
    over a ring pass — must match, including the causal clamp."""
    from pytorch_distributed_nn_tpu.ops.pallas.ring_attention import (
        STAT_LANES,
        _ring_block_pallas,
        _ring_block_reference,
    )

    ok = True
    rng = np.random.RandomState(2)
    BH, Tl, D, S = 8, 256, 128, 4  # 4-device ring, local seq 256
    q = jnp.asarray(rng.randn(BH, Tl, D).astype(np.float32) * 0.3)
    for causal in (True, False):
        for idx in range(S):  # device position in the ring
            m = jnp.full((BH, Tl, STAT_LANES), -1e30, jnp.float32)
            l = jnp.zeros((BH, Tl, STAT_LANES), jnp.float32)
            acc = jnp.zeros((BH, Tl, D), jnp.float32)
            m_r, l_r, acc_r = m, l, acc
            for i in range(S):  # ring steps: own block first
                src = (idx - i) % S
                k_blk = jnp.asarray(
                    rng.randn(BH, Tl, D).astype(np.float32) * 0.3)
                v_blk = jnp.asarray(
                    rng.randn(BH, Tl, D).astype(np.float32))
                offs = jnp.array([idx * Tl, src * Tl], jnp.int32)
                m, l, acc = _ring_block_pallas(
                    q, k_blk, v_blk, m, l, acc, offs, causal=causal,
                    block_q=128, block_k=128,
                    interpret=jax.default_backend() != "tpu")
                m_r, l_r, acc_r = _ring_block_reference(
                    q, k_blk, v_blk, m_r, l_r, acc_r, offs, causal=causal)
            got = np.asarray(acc / jnp.maximum(l[..., 0:1], 1e-30))
            want = np.asarray(acc_r / jnp.maximum(l_r[..., 0:1], 1e-30))
            err = float(np.abs(got - want).max())
            line_ok = err < 2e-2
            ok &= line_ok
            print(f"ring-block idx={idx}/{S} causal={causal}: "
                  f"max_err={err:.2e} {'OK' if line_ok else 'FAIL'}")
    return ok


def check_ring_bwd() -> bool:
    """The full fused ring path (Pallas forward + the per-step flash
    two-pass Pallas backward with lse replay) against autodiff of the
    dense reference, on a 1-device ring — the chip is single-device
    here, so this validates the kernels + custom_vjp plumbing on real
    hardware; the multi-device ring schedule (rotating dk/dv
    accumulators, causal flavor dispatch) is validated on the 8-device
    CPU interpret mesh by tests/test_sequence_parallel.py."""
    from pytorch_distributed_nn_tpu.parallel.sequence import (
        ring_attention,
    )
    from pytorch_distributed_nn_tpu.runtime.mesh import (
        MeshSpec,
        make_mesh,
    )
    from jax.sharding import PartitionSpec as P

    on_tpu = jax.default_backend() == "tpu"
    impl = "pallas" if on_tpu else "pallas_interpret"
    mesh = make_mesh(MeshSpec(seq=1, data=1))
    ok = True
    rng = np.random.RandomState(5)
    for (B, T, H, D, Hkv) in [(1, 1024, 4, 64, 4), (1, 1024, 4, 64, 2)]:
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(B, T, Hkv, D).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(B, T, Hkv, D).astype(np.float32))

        for causal in (True, False):
            def f_ring(q, k, v):
                def inner(a, b, c):
                    out = ring_attention(a, b, c, causal=causal,
                                         impl=impl)
                    return (out.astype(jnp.float32) ** 2).sum()

                mapped = jax.shard_map(
                    lambda a, b, c: jax.grad(
                        inner, argnums=(0, 1, 2))(a, b, c),
                    mesh=mesh, in_specs=(P(None, "seq"),) * 3,
                    out_specs=(P(None, "seq"),) * 3, check_vma=False,
                )
                return jax.jit(mapped)(q, k, v)

            def f_ref(q, k, v):
                kx = jnp.repeat(k, H // Hkv, axis=2)
                vx = jnp.repeat(v, H // Hkv, axis=2)
                to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
                    B * H, T, D)
                out = _attention_reference(to_bh(q), to_bh(kx),
                                           to_bh(vx), causal=causal)
                return (out.astype(jnp.float32) ** 2).sum()

            got = f_ring(q, k, v)
            want = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
            for gg, ww, name in zip(got, want, ("dq", "dk", "dv")):
                err = float(jnp.abs(gg - ww).max())
                line_ok = err < 2e-2
                ok &= line_ok
                print(f"ring-bwd T{T} H{H}/kv{Hkv} {name} "
                      f"causal={causal}: max_err={err:.2e} "
                      f"{'OK' if line_ok else 'FAIL'}")
    return ok


def check_long_context() -> bool:
    """The streamed flash kernel at 128k-512k tokens on the REAL chip
    (SURVEY.md §5 long-context row names 32k-512k; the CPU harness
    can't execute these — T^2 on one host core trips XLA CPU's
    collective rendezvous deadline, see __graft_entry__, which instead
    AOT-compiles the 128k seq-sharded ring step). A dense oracle at
    128k would materialize a 68 GB score matrix, so correctness at
    these lengths rides the small-T oracle checks above; this check
    proves the kernel's real-TPU tiling/DMA/VMEM behavior AT LENGTH:
    fwd+bwd execute, outputs and grads finite, throughput printed."""
    import time

    on_tpu = jax.default_backend() == "tpu"
    ok = True
    rng = np.random.RandomState(6)
    lengths = [1 << 17, 1 << 19] if on_tpu else [1 << 12]
    for T in lengths:
        B, H, D, Hkv = 1, 4, 64, 2
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(B, T, Hkv, D).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(B, T, Hkv, D).astype(np.float32))

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=True)
                    .astype(jnp.float32) ** 2).sum()

        grad_fn = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))
        (val, grads) = grad_fn(q, k, v)  # compile + warm
        float(val)  # fence the warm-up before the timer starts
        t0 = time.perf_counter()
        val, grads = grad_fn(q, k, v)
        jax.block_until_ready(grads)
        finite = bool(np.isfinite(float(val)))
        dt = time.perf_counter() - t0
        for g in grads:
            finite &= bool(jnp.isfinite(g).all())
        ok &= finite
        print(f"long-context flash fwd+bwd T={T}: {dt * 1e3:.1f} ms "
              f"({T / dt:.0f} tok/s) finite={finite} "
              f"{'OK' if finite else 'FAIL'}")
    return ok


def check_bn_stats() -> bool:
    """BatchNorm statistics kernels (ops/pallas/bn_stats.py) vs the jnp
    oracle, across the real ResNet channel geometries: C≥128 direct,
    C=64 lane-folded, and a (M % block)≠0 tail-masked case. The
    measured A/B keeps these OUT of the resnet50_dp default (XLA's
    conv+stats epilogue fusion wins — docs/design.md "ResNet-50 MFU"),
    but the kernels stay gated so the 'pallas' stats_impl stays
    correct."""
    from pytorch_distributed_nn_tpu.ops.pallas.bn_stats import (
        sum_and_dot,
        sum_and_sumsq,
    )

    ok = True
    rng = np.random.RandomState(11)
    # (N, H, W, C): C=64 exercises the fold, 7x7x512 the masked tail
    for shape in [(8, 14, 14, 256), (8, 28, 28, 64), (16, 7, 7, 512)]:
        x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 2,
                        jnp.bfloat16)
        dy = jnp.asarray(rng.randn(*shape).astype(np.float32),
                         jnp.bfloat16)
        axes = tuple(range(x.ndim - 1))
        xf = np.asarray(x, np.float32)
        dyf = np.asarray(dy, np.float32)
        s1, s2 = jax.jit(sum_and_sumsq)(x)
        d1, d2 = jax.jit(sum_and_dot)(dy, x)
        good = True
        for got, want in [(s1, xf.sum(axes)), (s2, (xf * xf).sum(axes)),
                          (d1, dyf.sum(axes)), (d2, (dyf * xf).sum(axes))]:
            good &= bool(np.allclose(np.asarray(got), want, rtol=2e-3,
                                     atol=2e-2 * np.sqrt(xf.size)))
        ok &= good
        print(f"bn_stats {shape}: {'OK' if good else 'FAIL'}")
    return ok


def check_prefix_attention() -> bool:
    """The prefill kernel against the jax.numpy recurrence at the served
    sizes in bf16: MLA's 64 heads of 192 / 128 against a row of 8,192 (a
    whole prompt with a padded tail, a suffix behind 6,144 restored
    rows), and grouped queries of 128 / 128 against a row of 4,096
    (Mistral's 32 heads to 8, K-EXAONE's 64 to 8 with a padded tail)."""
    import functools

    from pytorch_distributed_nn_tpu.ops.pallas import prefix_attention as pa

    if jax.default_backend() != "tpu":
        print("prefix_attention: skipped (the kernel needs the chip)")
        return True
    ok = True
    ks = jax.random.split(jax.random.key(8), 3)
    for H, Hkv, dk, S, T, first, real in [
            (64, 64, 192, 8192, 8192, 0, 6528),
            (64, 64, 192, 8192, 512, 6144, 512),
            (32, 8, 128, 4096, 4096, 0, 4096),
            (64, 8, 128, 4096, 2048, 1024, 1500)]:
        q = jax.random.normal(ks[0], (1, H, T, dk), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, Hkv, S, dk), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, Hkv, S, 128), jnp.bfloat16)
        pos = jnp.where(jnp.arange(T) < real, first + jnp.arange(T), -1)[None]
        kw = dict(scale=dk ** -0.5, block_q=min(pa.QUERY_BLOCK, T),
                  block_k=pa.KEY_BLOCK)
        got, want = (jax.jit(functools.partial(run, **kw))(q, k, v, pos)
                     for run in (pa._pallas, pa._blockwise))
        err = float(jnp.abs(got.astype(jnp.float32)
                            - want.astype(jnp.float32)).max())
        line_ok = err < 2e-2
        ok &= line_ok
        print(f"prefix_attention {H}/{Hkv} heads T{T} at {first}, {real} "
              f"real: max_err={err:.2e} {'OK' if line_ok else 'FAIL'}")
    return ok


def check_selective_scan() -> bool:
    """The Mamba prefill's kernel against ``lax.scan`` at Jamba2-3B's
    widths, ``c`` in bf16: the chat cell's smallest bucket with a padded
    tail (the state held bit for bit through it), a ragged call through
    the dispatcher's padding, and 1,024 positions from a state that is
    not zero."""
    import functools

    from pytorch_distributed_nn_tpu.nn import mamba

    if jax.default_backend() != "tpu":
        print("selective_scan: skipped (the kernel needs the chip)")
        return True
    ok = True
    ks = jax.random.split(jax.random.key(9), 6)
    a = -jnp.exp(jax.random.normal(ks[4], (16, 5120)))
    h = jax.random.normal(ks[5], (1, 16, 5120))
    for T, real in [(128, 77), (300, 300), (1024, 1024)]:
        dt = jnp.where(jnp.arange(T)[None, :, None] < real, jax.nn.softplus(
            jax.random.normal(ks[0], (1, T, 5120)) - 2.0), 0.0)
        c = jax.random.normal(ks[1], (1, T, 5120), jnp.bfloat16)
        b, co = (jax.random.normal(k, (1, T, 16)) for k in ks[2:4])
        (y, h1), (want_y, want_h) = (
            jax.jit(functools.partial(mamba.selective_scan,
                                      differentiable=differentiable))(
                h, dt, c, b, co, a) for differentiable in (False, True))
        _, held = mamba.selective_scan(
            h, *(x[:, :real] for x in (dt, c, b, co)), a,
            differentiable=False)
        err = float(jnp.abs(y - want_y).max() / jnp.abs(want_y).max())
        err_h = float(jnp.abs(h1 - want_h).max())
        line_ok = err < 1e-5 and err_h < 1e-5 and bool((held == h1).all())
        ok &= line_ok
        print(f"selective_scan T{T}, {real} real: y rel_err={err:.2e} "
              f"h max_err={err_h:.2e} {'OK' if line_ok else 'FAIL'}")
    return ok


def main() -> int:
    print(f"backend: {jax.default_backend()} devices: {jax.devices()}")
    if jax.default_backend() != "tpu":
        print("WARNING: not on TPU — validating fallbacks only")
    ok = (check_flash() & check_flash_grad() & check_quantize()
          & check_int8_matmul() & check_ring_block() & check_ring_bwd()
          & check_long_context() & check_bn_stats()
          & check_prefix_attention() & check_selective_scan())
    print("ALL OK" if ok else "FAILURES")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
