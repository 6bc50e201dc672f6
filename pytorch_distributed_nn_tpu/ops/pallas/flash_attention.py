"""Blockwise (flash) attention Pallas TPU kernel.

Replaces the reference's cuDNN/SDPA attention (SURVEY.md §2b ATen row)
with an HBM-friendly TPU kernel: Q blocks stay resident in VMEM while K/V
stream through, online softmax keeps running (max, denom) so the (T, T)
score matrix never materialises in HBM. bf16 operands hit the MXU; all
accumulation is f32.

On non-TPU backends (the CPU test mesh) :func:`flash_attention` falls back
to the jnp reference — same math, same signature — so CPU tests exercise
callers' integration while the kernel itself is validated on the real
chip (tests/test_pallas.py + bench).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_nn_tpu.ops.pallas import warn_reference_fallback

NEG_INF = -1e30


def _attention_reference(q, k, v, *, causal: bool):
    """jnp oracle: (BH, T, D) inputs."""
    T, S = q.shape[1], k.shape[1]
    logits = jnp.einsum(
        "btd,bsd->bts", q, k, preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, S), dtype=bool))
        logits = jnp.where(mask[None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bts,bsd->btd", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


STAT_LANES = 8  # minor dim of the m/l scratch (min f32 sublane tile)


def _stat_subl(nq: int) -> int:
    """Sublane-group height for the (BH, nq, block_q) lse/delta arrays.

    TPU block tiling needs the last two block dims divisible by (8, 128)
    or equal to the array dims, so a (1, block_q) per-row block is
    illegal whenever nq > 1, and the whole (nq, block_q) plane OOMs the
    16 MB scoped-vmem stack at T=512k (2026-08-01, first run: 2 MB x2
    stats x double-buffering). Group-of-8 rows satisfies the sublane
    tile and keeps stat VMEM residency T-independent (8*block_q f32)."""
    return min(8, nq)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, causal: bool, block_q: int, block_k: int,
                  subl: int):
    """One (bh, qi, kj) grid step. The kj grid dim iterates sequentially
    on TPU, so the f32 running stats (m, l, acc) live in VMEM scratch
    across k blocks: initialized at kj == 0, emitted at the last kj.
    Only one (block_q, D) Q tile and one (block_k, D) K/V tile are
    VMEM-resident per step — T is bounded by HBM, not VMEM."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: whole block in this Q block's future contributes nothing
    live = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        q = q * (q.shape[-1] ** -0.5)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == nk - 1)
    def _emit():
        m = m_scr[...][:, :1]
        l = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype
        )
        # logsumexp per row — the softmax stat the backward kernels
        # need to reconstruct p without a second online pass. Layout
        # (BH, nq, block_q) in sublane groups of ``subl`` rows (see
        # _stat_subl); this qi owns row qi % subl of its group block.
        lse_ref[0, pl.ds(qi % subl, 1)] = (
            m + jnp.log(jnp.maximum(l, 1e-30))
        )[:, 0][None, :]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_bhtd(q, k, v, *, causal: bool, block_q: int, block_k: int,
                interpret: bool = False):
    """(BH, T, D) flash attention via pallas_call (K/V streamed by the
    grid, so sequence length is not VMEM-bounded). Returns (out, lse).

    GQA-native: k/v may be (BKV, T, D) with BKV dividing BH — each KV
    head serves BH/BKV consecutive Q heads (row ``bh`` reads kv row
    ``bh // q_per_kv``), so grouped KV is streamed once per Q head
    *group*, never expanded in HBM."""
    BH, T, D = q.shape
    BKV = k.shape[0]
    if BH % BKV:
        raise ValueError(f"q heads {BH} not a multiple of kv heads {BKV}")
    q_per_kv = BH // BKV
    grid = (BH, pl.cdiv(T, block_q), pl.cdiv(T, block_k))
    subl = _stat_subl(grid[1])
    kernel = functools.partial(
        _flash_kernel, causal=causal, block_q=block_q, block_k=block_k,
        subl=subl,
    )
    if causal:
        # Dead (fully-future) K/V blocks are skipped by pl.when in the
        # kernel; clamping the index map to the last live block makes
        # Pallas elide their DMAs too (repeated block index => no copy),
        # saving ~half the streamed K/V bytes.
        def kv_map(bh, qi, kj):
            last_live = ((qi + 1) * block_q - 1) // block_k
            return (bh // q_per_kv, jnp.minimum(kj, last_live), 0)
    else:
        def kv_map(bh, qi, kj):
            return (bh // q_per_kv, kj, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_map,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, subl, block_q),
                         lambda bh, qi, kj: (bh, qi // subl, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, grid[1], block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # qi must be 'arbitrary': consecutive qi share one lse group
            # block (each writes its own row), and a megacore split over
            # a parallel qi would give each core a private copy with only
            # its own rows written — last writer wins
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * BH * T * T * D,
            # Q+O once each, K and V re-streamed once per Q block
            bytes_accessed=(2 * BH * T * D + 2 * BH * T * T // max(
                block_q, 1) * D) * q.dtype.itemsize,
            transcendentals=BH * T * T,
        ),
        interpret=interpret,
    )(q, k, v)


def _bwd_recompute(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   qi, kj, *, causal: bool, block_q: int, block_k: int,
                   subl: int):
    """Shared recompute for both backward passes: p from the saved lse
    and ds from the flash recurrence. Returns (q, k_blk, g_blk, p, ds)
    in f32 — the two kernels differ only in which products they
    accumulate from these. ``qi``'s stat row lives at qi % subl of the
    fetched (subl, block_q) group block (see _stat_subl)."""
    scale = q_ref.shape[-1] ** -0.5
    q = q_ref[0].astype(jnp.float32)
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    g_blk = g_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    row = pl.ds(qi % subl, 1)
    p = jnp.exp(s - lse_ref[0, row][0][:, None])
    dp = jnp.dot(g_blk, v_blk.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, row][0][:, None]) * scale
    return q, k_blk, g_blk, p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, causal: bool, block_q: int,
                   block_k: int, subl: int):
    """dq pass: fixed Q block, stream K/V blocks (same grid shape and
    causal DMA clamp as the forward). p is reconstructed from the
    forward's lse, so no online-softmax rescan is needed."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _body():
        _, k_blk, _, _, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qi, kj,
            causal=causal, block_q=block_q, block_k=block_k, subl=subl,
        )
        dq_scr[...] += jnp.dot(ds, k_blk,
                               preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                    block_q: int, block_k: int, nq: int, subl: int):
    """dk/dv pass: fixed K/V block, stream Q blocks (roles swapped —
    the accumulators live with the K/V tile). The inner grid dim is
    ``g * nq + qi`` over the KV head's Q-head group (GQA): the group
    reduction happens in the same accumulator as the Q-block sum."""
    kj = pl.program_id(1)
    inner = pl.program_id(2)
    n_inner = pl.num_programs(2)
    qi = inner % nq

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # causal: Q blocks entirely before this K block see none of it
    live = ((qi + 1) * block_q - 1 >= kj * block_k) if causal else True

    @pl.when(live)
    def _body():
        q, _, g_blk, p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qi, kj,
            causal=causal, block_q=block_q, block_k=block_k, subl=subl,
        )
        dv_scr[...] += jnp.dot(p.T, g_blk,
                               preferred_element_type=jnp.float32)
        dk_scr[...] += jnp.dot(ds.T, q,
                               preferred_element_type=jnp.float32)

    @pl.when(inner == n_inner - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_k", "out_dtype",
                                             "interpret"))
def _flash_bwd_pallas(q, k, v, g, lse, delta, *, causal: bool,
                      block_q: int, block_k: int, out_dtype=None,
                      interpret: bool = False):
    """(dq, dk, dv) via the two-pass Pallas backward. GQA-native like the
    forward: k/v (BKV, T, D) with BKV | BH; dk/dv come back grouped —
    the dk/dv grid iterates the group's Q heads inside each KV block so
    their contributions sum in the VMEM accumulator, which is exactly
    the head-group reduction an expanded-KV backward would need a
    separate sum for.

    ``out_dtype`` overrides the gradient dtype (ring attention
    accumulates per-step contributions in f32 across ring rounds);
    ``interpret`` runs the kernels under the Pallas interpreter (CPU
    correctness path for the ring backward)."""
    BH, T, D = q.shape
    BKV = k.shape[0]
    q_per_kv = BH // BKV
    nq = pl.cdiv(T, block_q)
    nk = pl.cdiv(T, block_k)
    dq_dtype = out_dtype or q.dtype
    dkv_dtype = out_dtype or k.dtype

    subl = _stat_subl(nq)
    q_map = lambda bh, qi, kj: (bh, qi, 0)  # noqa: E731
    # stats: one (subl, block_q) sublane group per subl consecutive qi —
    # VMEM use is T-independent (see _stat_subl)
    stat_map = lambda bh, qi, kj: (bh, qi // subl, 0)  # noqa: E731
    stat_block = (1, subl, block_q)
    if causal:
        def kv_map(bh, qi, kj):
            last_live = ((qi + 1) * block_q - 1) // block_k
            return (bh // q_per_kv, jnp.minimum(kj, last_live), 0)
    else:
        def kv_map(bh, qi, kj):
            return (bh // q_per_kv, kj, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, subl=subl),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, D), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec(stat_block, stat_map, memory_space=pltpu.VMEM),
            pl.BlockSpec(stat_block, stat_map, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), dq_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    # dk/dv pass: for a fixed K/V block, the inner grid dim walks the
    # group's Q heads and their Q blocks (inner = g * nq + qi) so every
    # contribution to this KV head lands in one VMEM accumulator.
    kv_fix = lambda bkv, kj, inner: (bkv, kj, 0)  # noqa: E731
    if causal:
        # clamp dead (fully-future-of-this-KV-block) Q rows to the
        # first live one: the kernel's `live` gate skips them, and the
        # repeated block index lets Pallas elide their DMAs — stats
        # ride the same clamped row's group so dead steps copy nothing
        # either (on live steps the clamp is the identity, so the
        # fetched group always holds the kernel's qi % subl row)
        def _qi(kj, inner):
            return jnp.maximum(inner % nq, (kj * block_k) // block_q)
    else:
        def _qi(kj, inner):
            return inner % nq

    q_stream = lambda bkv, kj, inner: (  # noqa: E731
        bkv * q_per_kv + inner // nq, _qi(kj, inner), 0)
    stat_fix = lambda bkv, kj, inner: (  # noqa: E731
        bkv * q_per_kv + inner // nq, _qi(kj, inner) // subl, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          subl=subl),
        grid=(BKV, nk, q_per_kv * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_stream,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_fix, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_fix, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, D), q_stream,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(stat_block, stat_fix, memory_space=pltpu.VMEM),
            pl.BlockSpec(stat_block, stat_fix, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), kv_fix, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_fix, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, T, D), dkv_dtype),
            jax.ShapeDtypeStruct((BKV, T, D), dkv_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _flash_bwd_blockwise(q, k, v, o, g, *, causal: bool,
                         block_q: int = 128):
    """CPU-testable oracle of the backward recurrence the Pallas pair
    (:func:`_bwd_dq_kernel` / :func:`_bwd_dkv_kernel`) implements:
    dv = pᵀ·dO; ds = p∘(dO·vᵀ − Δ); dq = ds·k; dk = dsᵀ·q with
    Δ = rowsum(dO∘O), blockwise over Q via ``lax.scan``. Not a
    production path — tests/test_pallas_fallbacks.py validates this
    math against jax AD on CPU, and scripts/validate_tpu_kernels.py
    validates the Pallas kernels against jax AD on the chip."""
    BH, T, D = q.shape
    scale = D ** -0.5
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), -1)

    nb = T // block_q

    def body(carry, i):
        dk, dv = carry
        row = i * block_q
        qb = jax.lax.dynamic_slice_in_dim(qf, row, block_q, 1)
        gb = jax.lax.dynamic_slice_in_dim(
            g.astype(jnp.float32), row, block_q, 1
        )
        db = jax.lax.dynamic_slice_in_dim(delta, row, block_q, 1)
        s = jnp.einsum("btd,bsd->bts", qb, kf,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = row + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, T), 0
            )
            k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 1)
            s = jnp.where((q_pos >= k_pos)[None], s, NEG_INF)
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        p = p / p.sum(-1, keepdims=True)  # (BH, block_q, T)
        dv = dv + jnp.einsum("bts,btd->bsd", p, gb,
                             preferred_element_type=jnp.float32)
        dp = jnp.einsum("btd,bsd->bts", gb, vf,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - db[..., None]) * scale
        dqb = jnp.einsum("bts,bsd->btd", ds, kf,
                         preferred_element_type=jnp.float32)
        dk = dk + jnp.einsum("bts,btd->bsd", ds, qb,
                             preferred_element_type=jnp.float32)
        return (dk, dv), dqb

    dk0 = jnp.zeros_like(kf)
    dv0 = jnp.zeros_like(vf)
    (dk, dv), dq_blocks = jax.lax.scan(body, (dk0, dv0), jnp.arange(nb))
    # (nb, BH, block_q, D) -> (BH, T, D)
    dq = dq_blocks.transpose(1, 0, 2, 3).reshape(BH, T, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_diff(qb, kb, vb, causal, block_q, block_k):
    """Differentiable wrapper: Pallas forward, Pallas two-pass backward
    (dq; dk/dv) reconstructing p from the forward's saved lse — neither
    direction ever materializes the (T, T) score matrix, and AD never
    touches a pallas_call."""
    out, _ = _flash_bhtd(qb, kb, vb, causal=causal, block_q=block_q,
                         block_k=block_k)
    return out


def _flash_diff_fwd(qb, kb, vb, causal, block_q, block_k):
    out, lse = _flash_bhtd(qb, kb, vb, causal=causal, block_q=block_q,
                           block_k=block_k)
    return out, (qb, kb, vb, out, lse)


def _flash_diff_bwd(causal, block_q, block_k, res, g):
    qb, kb, vb, out, lse = res
    BH, T, _ = qb.shape
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), -1)
    delta = delta.reshape(BH, T // block_q, block_q)  # lse's layout
    return _flash_bwd_pallas(
        qb, kb, vb, g.astype(qb.dtype), lse, delta,
        causal=causal, block_q=block_q, block_k=block_k,
    )


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def _pick_block(T: int, want: int) -> int | None:
    """Largest block size <= want that divides T (v5e sweeps: at T=32k,
    512x512 is 3.8x faster than 128x128 and 1024x1024 another 1.33x over
    512x512 — bigger MXU tiles, fewer grid steps; 2048 blocks fail to
    compile at D=128, over VMEM). None = no candidate divides T."""
    for b in (want, 512, 256, 128):
        if b <= want and T % b == 0:
            return b
    return None


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 1024,
                    block_k: int = 1024):
    """(B, T, H, D) attention; k/v may carry fewer heads (GQA) as long
    as Hkv divides H — grouped KV is streamed natively (each KV tile
    serves its whole Q-head group), cutting streamed KV bytes by
    H/Hkv versus expanding. Falls back to the jnp reference off TPU.
    Differentiable: the backward is the Pallas two-pass kernel pair
    (dq, then dk/dv) replaying p from the forward's saved lse; dk/dv
    come back grouped, so AD flows to the unexpanded projections with
    no extra head-sum."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(
            f"flash_attention needs kv heads dividing q heads "
            f"({Hkv} vs {H})"
        )
    if k.shape[1] != T:
        raise ValueError(
            f"flash_attention is self-attention only (kv len "
            f"{k.shape[1]} != q len {T}); use impl='xla' for cross-length"
        )

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, T, D)

    def from_bh(x):
        return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    def expand(x):  # row bh reads kv row bh // q_per_kv — same layout
        return jnp.repeat(x, H // Hkv, axis=0)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    if jax.default_backend() != "tpu":
        return from_bh(_attention_reference(qb, expand(kb), expand(vb),
                                            causal=causal))
    bq = _pick_block(T, min(block_q, T))
    bk = _pick_block(T, min(block_k, T))
    if bq is None or bk is None:
        warn_reference_fallback("flash_attention", tuple(q.shape))
        return from_bh(_attention_reference(qb, expand(kb), expand(vb),
                                            causal=causal))
    return from_bh(
        _flash_diff(qb, kb, vb, causal, bq, bk)
    )
