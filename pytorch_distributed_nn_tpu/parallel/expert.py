"""Expert parallelism (MoE) — the EP row of SURVEY.md §2c.

The reference has no MoE support; the task mandates the complete
parallelism inventory, so expert parallelism is first-class here. The
design is the GShard/Switch capacity-based formulation, which is the
TPU-idiomatic one:

- **Routing as einsums, not gather/scatter.** Token→expert assignment is
  expressed with dense one-hot ``dispatch``/``combine`` tensors and
  ``einsum`` contractions. Every op is a static-shape matmul — it lands
  on the MXU and XLA can fuse/partition it; there is no data-dependent
  control flow anywhere (SURVEY's "no dynamic shapes under jit" rule).
- **EP as a layout, not a protocol.** Expert weights are stacked
  ``(E, d, ff)`` and sharded over the ``expert`` mesh axis by
  :mod:`~pytorch_distributed_nn_tpu.parallel.sharding_rules`; tokens stay
  sharded over the data axes. XLA's SPMD partitioner then inserts the
  token all-to-all (dispatch) and its reverse (combine) over ICI — the
  same way the ZeRO strategy gets its all-gather/reduce-scatter for free
  (parallel/zero.py). The explicit ``shard_map`` form of the dispatch is
  :func:`ep_dispatch` / :func:`ep_combine`, the pedagogical analogue of
  ``dp_explicit``.
- **Capacity, not queues.** Each expert processes a fixed ``capacity``
  of tokens per step; overflow tokens are dropped (their combine weight
  is zero, so they pass through the residual unchanged) — the standard
  static-shape trade the Switch/GShard papers make.

The auxiliary load-balance loss is sown into the ``"losses"`` collection;
the shared train-step path (parallel/dp.py ``forward``) collects and adds
it to the task loss.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.ops import collectives as cc
from pytorch_distributed_nn_tpu.ops.pallas import grouped_experts as kernel
from pytorch_distributed_nn_tpu.runtime.mesh import AXIS_EXPERT


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Routing:
    """Result of :func:`top_k_routing` for one group of N tokens."""

    dispatch: jnp.ndarray  # (N, E, C) 0/1 — token n → slot c of expert e
    combine: jnp.ndarray  # (N, E, C) float — gate weights for the return trip
    aux_loss: jnp.ndarray  # scalar load-balance loss (Switch formulation)
    fraction_dropped: jnp.ndarray  # scalar, tokens over capacity


def expert_capacity(num_tokens: int, num_experts: int, k: int,
                    capacity_factor: float) -> int:
    """Static per-expert slot count: ceil(k·N/E · factor), floored at 1."""
    return max(1, math.ceil(num_tokens * k * capacity_factor / num_experts))


def top_k_routing(router_logits: jnp.ndarray, *, k: int,
                  capacity: int) -> Routing:
    """Capacity-based top-k routing (GShard §3.2 scheme, vectorised).

    ``router_logits``: (N, E) float32. Tokens claim expert slots in token
    order (position-in-expert via cumulative sum); a token whose chosen
    expert is already at capacity is dropped for that expert. Gates are
    the softmax probabilities of the chosen experts, renormalised over
    the k choices (Mixtral convention) *before* capacity dropping.
    """
    N, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (N, k) each
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    # one-hot expert choice per (choice, token): (k, N, E)
    choice_mask = jax.nn.one_hot(expert_idx.T, E, dtype=jnp.float32)

    # Position of each (choice, token) in its expert's queue. Choices are
    # ranked choice-major then token-major: all first choices claim slots
    # before any second choice (GShard's priority rule), so within one
    # choice level positions are a per-token cumsum, offset by every
    # earlier level's total claim count.
    pos_within = jnp.cumsum(choice_mask, axis=1) - choice_mask  # (k, N, E)
    prior_counts = jnp.cumsum(choice_mask.sum(axis=1), axis=0) \
        - choice_mask.sum(axis=1)  # (k, E): claims from earlier levels
    position = pos_within + prior_counts[:, None, :]  # (k, N, E)
    position = (position * choice_mask).sum(-1)  # (k, N) scalar slot idx

    fits = position < capacity  # (k, N)
    kept = fits.T * (gate_vals > 0)  # (N, k)

    # combine[n, e, c] = gate weight of token n at slot c of expert e
    slot_onehot = jax.nn.one_hot(position.T.astype(jnp.int32), capacity,
                                 dtype=jnp.float32)  # (N, k, C)
    expert_onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    combine = jnp.einsum(
        "nk,nke,nkc->nec",
        gate_vals * kept.astype(jnp.float32), expert_onehot, slot_onehot,
    )
    dispatch = (combine > 0.0).astype(router_logits.dtype)

    # Switch load-balance loss: E · Σ_e f_e·P_e, where f_e is the fraction
    # of (token, choice) assignments routed to e and P_e the mean router
    # probability. Minimised (=1) at uniform routing.
    f = choice_mask.sum(axis=(0, 1)) / (N * k)  # fraction of assignments
    p = probs.mean(axis=0)  # (E,)
    aux = E * jnp.sum(f * p)

    dropped = 1.0 - kept.sum() / jnp.asarray(N * k, jnp.float32)
    return Routing(dispatch=dispatch, combine=combine.astype(
        router_logits.dtype), aux_loss=aux, fraction_dropped=dropped)


class MoEMLP(nn.Module):
    """Mixture-of-experts FFN block (drop-in for a dense MLP).

    Expert weights are stacked on a leading E dim — ``wi (E, d, ff)``,
    ``wo (E, ff, d)`` — which the layout rules shard over the ``expert``
    mesh axis (sharding_rules.EP_RULES). All compute is batched einsum.

    Routing is **grouped** (GShard §3.1): tokens are split into groups of
    at most ``group_size`` (never crossing a sequence boundary) and each
    group is routed independently with capacity ``ceil(k·g·cf/E)``. The
    dispatch/combine tensors are then (G, g, E, C) — O(N·g·k·cf) memory
    instead of the O(N²·k·cf) a single global group would cost, which is
    what keeps batch 32 × seq 1024 runnable on a 16 GB chip.
    """

    num_experts: int = 8
    mlp_dim: int = 3072
    k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    group_size: int = 1024  # max tokens per routing group
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, S, d = x.shape
        E = self.num_experts
        g = min(self.group_size, S)
        if S % g:
            raise ValueError(
                f"seq_len {S} not divisible by routing group size {g}"
            )
        G = B * (S // g)
        tokens = x.reshape(G, g, d)

        # Router in fp32: small matmul, numerically load-bearing.
        router_logits = nn.Dense(
            E, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype, name="router",
        )(tokens.astype(jnp.float32))  # (G, g, E)
        C = expert_capacity(g, E, self.k, self.capacity_factor)
        routing = jax.vmap(
            partial(top_k_routing, k=self.k, capacity=C)
        )(router_logits)  # fields batched over G

        wi = self.param(
            "wi", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E, d, self.mlp_dim), self.param_dtype,
        )
        wo = self.param(
            "wo", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E, self.mlp_dim, d), self.param_dtype,
        )

        # dispatch: (G,g,E,C)×(G,g,d) → (E, G·C, d). Under EP sharding
        # this einsum is where XLA inserts the token all-to-all.
        expert_in = jnp.einsum(
            "gnec,gnd->egcd", routing.dispatch.astype(self.dtype),
            tokens.astype(self.dtype),
        ).reshape(E, G * C, d)
        h = jnp.einsum("esd,edf->esf", expert_in, wi.astype(self.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum(
            "esf,efd->esd", h, wo.astype(self.dtype)
        ).reshape(E, G, C, d)
        out = jnp.einsum(
            "gnec,egcd->gnd", routing.combine.astype(self.dtype), expert_out
        )

        # Collected by parallel/dp.forward into the train loss; a no-op
        # when the collection isn't mutable (eval / non-MoE callers).
        # Per-step drop diagnostics live on the Routing value
        # (fraction_dropped) for direct-layer users; they are not sown.
        self.sow("losses", "moe_aux",
                 self.aux_loss_weight * routing.aux_loss.mean(),
                 reduce_fn=lambda a, b: a + b, init_fn=lambda: jnp.float32(0))
        return out.reshape(B, S, d)


# held experts from which a layer's pairs go through ``grouped_experts``
GROUPED_FROM = 32


def experts_grouped(held: int) -> bool:
    """Whether a layer that holds ``held`` experts computes its pairs
    sorted by expert in one routine (:mod:`ops.pallas.grouped_experts`)
    or in a loop an expert unrolled into the program, each reading its
    static column block. A rule on ``held`` alone.

    Measured at SDAR's widths (d 2048, ff 768, 8 picks of 128, a round
    of 256 positions, a rank's share held; ms a layer on a v5e, the
    unrolled loop against the kernel, and the seconds the first call
    took to compile; PERF.md sec. 6, PR 43)::

        held    16      32      64      128
        loop    0.556   1.085   2.196   4.300    3.9 - 28.9 s
        kernel  0.414   0.647   1.093   1.927    1.1 - 2.5 s

    The loop costs ~34 us an expert whatever it holds and its text
    grows with ``held`` (896 ``while``s in SDAR's seven-layer program:
    110 s to compile where the grouped form takes 6, PR 42); the kernel
    26 us at 16 experts and 15 at 128, so it is ahead on every line,
    the first too. The boundary stands at 32 all the same: the layers
    that hold 12 to 16 are LongCat's, K-EXAONE's and A.X-K1's, at other
    widths (LongCat's expert is 75 MB where SDAR's is 9.4: blocks that
    must go in chunks), in programs their cells were measured with and
    ``tests/data/serve_program_digests.json`` pins. Moving them is a
    claim of its own, judged on those three cells (ROADMAP S12 (c))."""
    return held >= GROUPED_FROM


def _count_execution(execution: str):
    """One more expert layer a program was traced with."""
    get_registry().counter(
        "held_experts_calls_total", "expert layers a program was traced "
        "with, by execution", ("execution",)).inc(execution=execution)


def _unrolled_experts(a, out, member, w_held, counts, w_gate, w_up, w_down,
                      *, token_block: int, dtype):
    """``out`` (N, d) float32 with every held expert's term added: a
    loop a held expert in the program's text, each over the blocks of
    ``token_block`` tokens that picked it, its column block a static
    slice."""
    (N, d), held, ff = a.shape, counts.shape[0], w_down.shape[0]
    tb = min(token_block, N)
    n_pad = -(-N // tb) * tb
    # column j: the tokens that picked expert j first, in token order
    order = jnp.argsort(~member, axis=0, stable=True).astype(jnp.int32)
    order = jnp.pad(order, ((0, n_pad - N), (0, 0)))

    def cols(w, j, width):
        return w[:, j * width:(j + 1) * width]

    def run_expert(j, out):
        def one_block(b, out):
            rows = jax.lax.dynamic_slice(order[:, j], (b * tb,), (tb,))
            live = b * tb + jnp.arange(tb) < counts[j]
            xb = a[rows]
            wg = cols(w_gate, j, ff).astype(dtype)
            wu = cols(w_up, j, ff).astype(dtype)
            wd = cols(w_down, j, d).astype(dtype)
            h = nn.silu(xb @ wg) * (xb @ wu)
            yb = jnp.dot(h, wd, preferred_element_type=jnp.float32)
            wt = jnp.where(live, w_held[rows, j], 0.0)
            return out.at[rows].add(yb * wt[:, None])

        blocks = (counts[j] + tb - 1) // tb
        return jax.lax.fori_loop(0, blocks, one_block, out)

    for j in range(held):
        out = run_expert(j, out)
    return out


def _grouped_experts(a, out, expert, here, w, counts, w_gate, w_up, w_down,
                     *, num_experts: int, dtype):
    """``out`` with the picks computed here added, sorted by expert in
    one routine. ``expert`` (N, k) is each pick's held expert where
    ``here`` (N, k)."""
    (N, d), k = a.shape, expert.shape[1]
    held, ff = counts.shape[0], w_down.shape[0]
    a = a.astype(dtype)
    _count_execution(kernel.execution(N, k, num_experts, d, ff, a.dtype,
                                      w_gate.dtype)[0])
    return out + kernel.grouped_experts(
        a, jnp.where(here, expert, held).astype(jnp.int32), w, counts,
        w_gate, w_up, w_down, num_experts=num_experts)


class HeldExpertsMoE(nn.Module):
    """Dropless top-k MoE as ONE rank of an expert-parallel deployment.

    The layer is told which experts it holds: ``num_experts`` routed
    experts are spread over ``ep_size`` ranks and this one, ``ep_rank``,
    holds ``num_experts / ep_size`` of them, experts
    ``[ep_rank * held, (ep_rank + 1) * held)``. The router keeps its full
    width (every routed expert plus ``num_zero_experts`` zero-compute,
    identity experts, LongCat-Flash's, arXiv:2509.01322 sec. 2.1) and its
    ``k`` picks a token::

        p = softmax(float32(x) W_r);  S = top_k(p + b);  w_e = scaling * p_e
        y = sum_{e in S, held here} w_e SwiGLU_e(x)  +  sum_{e in S, zero} w_e x

    ``b`` is the selection bias (used to choose, not to weigh); weights
    are not renormalised. The terms of the experts held by other ranks
    are left out: under expert parallelism they arrive through the
    combine all-to-all, which one rank alone does not run.

    That is LongCat-Flash's router, the defaults. The router of the
    DeepSeek-V3 line (K-EXAONE's) differs in three fields:
    ``scoring="sigmoid"`` makes the scores ``p = sigmoid(float32(x) W_r)``;
    ``renormalize=True`` divides a token's weights by the sum of its
    ``k`` picked scores, *all* of them, held here or not (every rank
    computes the same denominator, so the ranks' parts still add up),
    ``w_e = scaling * p_e / (sum_{e' in S} p_e' + 1e-20)``; and
    ``routed_scaling`` is its 2.5.

    With ``n_group`` > 1 the selection is group-limited (DeepSeek-V3's,
    A.X-K1's): the routed experts form ``n_group`` groups of consecutive
    experts, a group scores the sum of its two best ``p + b``, and only
    the experts of the ``topk_group`` best groups can be picked (the
    others read 0 in the top-k). ``n_group`` 1 is the selection above,
    the same program.

    Nothing is dropped and no row's result depends on its batch
    neighbours, so the layer serves through a decode cache (``MoEMLP``'s
    capacity routing cannot). Cost follows the token-expert pairs routed
    here, not tokens x experts held, in one of two forms that
    :func:`experts_grouped` picks by ``held`` alone. A layer that holds
    few experts (12 to 16 of LongCat's, K-EXAONE's, A.X-K1's): for each
    held expert the tokens that picked it are gathered in blocks of
    ``token_block`` rows and a loop whose trip count is that expert's
    number of blocks runs the three matrix products, a loop an expert
    in the program's text. A layer that holds many (SDAR's 128): the
    pairs are sorted by expert, each expert's rows padded to whole
    tiles, and one routine walks the live tiles
    (:func:`ops.pallas.grouped_experts.grouped_experts`: a Pallas kernel
    on a TPU, a ``fori_loop`` elsewhere). In both an expert no token
    picked is not read, and the trip count is the data's: forward only.

    Expert kernels are laid side by side with the contracted axis
    first, ``(d, held * ff)`` and ``(ff, held * d)``: expert j's matrix
    is the tile-aligned column block ``[j * ff, (j + 1) * ff)``. Both
    forms read it in place: the unrolled loop as a static slice that XLA
    takes as the product's operand, the kernel through its index maps,
    which hand a grid step block ``j`` of the columns for the expert
    ``j`` the step's tile belongs to. (Stacked as ``(d, held, ff)`` the
    TPU's tiled layout interleaves the experts and every use copies the
    25 MB matrix out first; a ``dynamic_slice`` of the side-by-side
    form inside a rolled loop copied each expert's 9.4 MB too, PERF.md
    sec. 6, PR 43.) An initialiser that scales by ``shape[0]`` scales
    by the fan-in.

    ``token_mask`` (B, T) bool marks the real tokens (not the padding of
    a bucketed prefill, not a retired decode row): the others reach no
    expert and no counter. Returns ``(y, counts)``; ``counts`` is uint32
    ``[picks, picks to zero experts, pairs computed on held experts,
    distinct held experts touched]`` over the real tokens of this call
    and, with ``n_group`` > 1, a fifth: the distinct groups each token's
    picks fell in, summed (at most ``topk_group`` a token).
    """

    num_experts: int = 512
    num_zero_experts: int = 0
    mlp_dim: int = 2048
    k: int = 12
    routed_scaling: float = 1.0
    scoring: str = "softmax"      # or "sigmoid"
    renormalize: bool = False     # weights over the sum of all k picks
    n_group: int = 1              # groups of consecutive routed experts
    topk_group: int = 1           # groups a token may pick from
    ep_size: int = 1
    ep_rank: int = 0
    token_block: int = 128     # rows a block of the unrolled loop
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, token_mask=None):
        B, T, d = x.shape
        if self.num_experts % self.ep_size \
                or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"{self.num_experts} experts over ep_size {self.ep_size}, "
                f"rank {self.ep_rank}")
        grouped = self.n_group > 1
        if grouped and (self.num_zero_experts
                        or self.num_experts % self.n_group
                        or not 0 < self.topk_group <= self.n_group):
            raise ValueError(
                f"{self.num_experts} routed and {self.num_zero_experts} "
                f"zero experts in {self.n_group} groups, "
                f"{self.topk_group} kept")
        held = self.num_experts // self.ep_size
        first = self.ep_rank * held
        N = B * T
        a = x.reshape(N, d)
        real = jnp.ones((N,), bool) if token_mask is None \
            else token_mask.reshape(N)

        # router in float32 at full precision: which twelve of 768 win
        # hangs on differences a bf16 product would round away
        logits = nn.Dense(
            self.num_experts + self.num_zero_experts, use_bias=False,
            dtype=jnp.float32, param_dtype=self.param_dtype,
            precision=jax.lax.Precision.HIGHEST, name="router",
        )(a.astype(jnp.float32))
        ff = self.mlp_dim
        side_by_side = nn.initializers.lecun_normal()   # fan-in: shape[0]
        w_gate = self.param("experts_gate", side_by_side,
                            (d, held * ff), self.param_dtype)
        w_up = self.param("experts_up", side_by_side,
                          (d, held * ff), self.param_dtype)
        w_down = self.param("experts_down", side_by_side,
                            (ff, held * d), self.param_dtype)
        if self.is_initializing():
            return x, jnp.zeros((5 if grouped else 4,), jnp.uint32)

        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router scoring {self.scoring!r}")
        probs = jax.nn.softmax(logits, axis=-1) \
            if self.scoring == "softmax" else jax.nn.sigmoid(logits)
        choose = probs
        if self.has_variable("buffers", "selection_bias"):
            choose = probs + self.get_variable("buffers", "selection_bias")
        if grouped:
            size = self.num_experts // self.n_group
            by_group = choose.reshape(N, self.n_group, size)
            best_two = jax.lax.top_k(by_group, 2)[0].sum(axis=-1)
            _, keep = jax.lax.top_k(best_two, self.topk_group)
            kept = (keep[:, :, None] == jnp.arange(self.n_group)).any(axis=1)
            choose = jnp.where(kept[:, :, None], by_group, 0.0) \
                .reshape(N, self.num_experts)
        _, idx = jax.lax.top_k(choose, self.k)                    # (N, k)
        w = jnp.take_along_axis(probs, idx, axis=1)
        if self.renormalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * self.routed_scaling
        w = jnp.where(real[:, None], w, 0.0)

        # zero-compute experts: the token itself, where it lives
        is_zero = idx >= self.num_experts
        out = jnp.sum(jnp.where(is_zero, w, 0.0), axis=-1, keepdims=True) \
            * a.astype(jnp.float32)

        # held experts: weight and membership of every (token, expert)
        hit = (idx[:, :, None] - first == jnp.arange(held)) \
            & real[:, None, None]                                 # (N, k, held)
        w_held = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
        member = hit.any(axis=1)                                  # (N, held)
        counts = member.sum(axis=0).astype(jnp.int32)             # (held,)
        if experts_grouped(held):
            out = _grouped_experts(
                a, out, idx - first, hit.any(axis=2), w, counts, w_gate,
                w_up, w_down, num_experts=self.num_experts, dtype=self.dtype)
        else:
            _count_execution("unrolled_loop")
            out = _unrolled_experts(
                a, out, member, w_held, counts, w_gate, w_up, w_down,
                token_block=self.token_block, dtype=self.dtype)

        n_real = real.sum()
        stats = jnp.stack([
            n_real * self.k, (is_zero & real[:, None]).sum(),
            counts.sum(), (counts > 0).sum()]).astype(jnp.uint32)
        if grouped:
            in_group = (idx[:, :, None] // size
                        == jnp.arange(self.n_group)).any(axis=1)
            stats = jnp.concatenate([stats, jnp.stack([
                (in_group & real[:, None]).sum()]).astype(jnp.uint32)])
        return out.astype(self.dtype).reshape(B, T, d), stats


# ---------------------------------------------------------------------------
# Explicit shard_map EP transport (pedagogical parity with dp_explicit):
# the hand-rolled all-to-all the compiler path does implicitly.
# ---------------------------------------------------------------------------

def ep_dispatch(expert_in, *, axis: str = AXIS_EXPERT):
    """(E, C, d) with E global → (E/n, n·C, d) local expert view.

    Inside ``shard_map`` each device holds its tokens' contributions to
    *all* E experts; this all-to-all re-partitions so each device holds
    *its* E/n experts' slots from all n peers — ``dist.all_to_all`` in
    the reference's vocabulary (SURVEY.md §2c EP row).
    """
    n = cc.axis_size(axis)
    E, C, d = expert_in.shape
    if E % n:
        raise ValueError(f"experts {E} not divisible by axis size {n}")
    out = cc.all_to_all(expert_in, axis, split_axis=0, concat_axis=0)
    # (E, C, d) → rows grouped as n blocks of E/n experts: reorder to
    # (E/n, n·C, d) so each local expert sees one contiguous slot buffer.
    return out.reshape(n, E // n, C, d).transpose(1, 0, 2, 3) \
        .reshape(E // n, n * C, d)


def ep_combine(expert_out, *, axis: str = AXIS_EXPERT):
    """Inverse of :func:`ep_dispatch`: (E/n, n·C, d) → (E, C, d)."""
    n = cc.axis_size(axis)
    El, nC, d = expert_out.shape
    C = nC // n
    x = expert_out.reshape(El, n, C, d).transpose(1, 0, 2, 3) \
        .reshape(n * El, C, d)
    return cc.all_to_all(x, axis, split_axis=0, concat_axis=0)
