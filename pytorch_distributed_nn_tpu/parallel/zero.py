"""Sharded data parallelism (ZeRO / FSDP) — BASELINE.json config 5:
"allgather params + reduce-scatter grads" — and the shared compiler-
sharded step used by plain DP and tensor parallelism.

The reference implements sharded DP imperatively: gather each layer's
shards before use, reduce-scatter gradients after backward, local shard
optimizer step (SURVEY.md §3.4). TPU-native design is *declarative*:
parameters and optimizer state are laid out per
:mod:`~pytorch_distributed_nn_tpu.parallel.sharding_rules`, the train
step is the ordinary DP step, and XLA's SPMD partitioner inserts exactly
those all-gathers (scheduled ahead of first use) and reduce-scatters (on
the gradient sum) — plus the weight-update sharding of arXiv 2004.13336
(PAPERS.md): the optimizer update runs on the 1/n shard each device owns.

Stages (ParallelConfig.zero_stage):
- 0: nothing sharded over ``fsdp`` — plain DP layout (used by the 'dp'
  strategy; tensor-parallel rules still apply when mesh.tensor > 1);
- 1: optimizer state sharded, params replicated (ZeRO-1);
- 3: params + optimizer state sharded (ZeRO-3/FSDP). (ZeRO-2 is
  meaningless under XLA: gradients never materialise unsharded unless
  the schedule wants them to.)
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_nn_tpu.parallel import dp
from pytorch_distributed_nn_tpu.parallel.sharding_rules import (
    path_str,
    spec_for,
)
from pytorch_distributed_nn_tpu.runtime.mesh import (
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_TENSOR,
    batch_pspec,
    global_device_put,
)
from pytorch_distributed_nn_tpu.train.state import TrainState


def state_shardings(state: TrainState, mesh: Mesh, *, stage: int = 3):
    """NamedSharding for every TrainState leaf via the layout rules.

    Optimizer-state paths embed the parameter paths (optax moment trees
    mirror the params tree), so TP/fsdp rules hit them identically and
    moments land with their params.
    """
    tensor = mesh.shape[AXIS_TENSOR]
    fsdp = mesh.shape[AXIS_FSDP]
    expert = mesh.shape[AXIS_EXPERT]

    def shard_tree(tree, *, use_fsdp: bool):
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: NamedSharding(
                mesh,
                spec_for(path_str(kp), tuple(x.shape), tensor=tensor,
                         fsdp=fsdp if use_fsdp else 1, expert=expert),
            ),
            tree,
        )

    return state.replace(
        step=NamedSharding(mesh, P()),
        rng=NamedSharding(mesh, P()),
        params=shard_tree(state.params, use_fsdp=stage >= 3),
        model_state=shard_tree(state.model_state, use_fsdp=False),
        opt_state=shard_tree(state.opt_state, use_fsdp=stage >= 1),
    )


def _split_microbatches(x, accum: int, n_shards: int, micro_sh):
    """(B, ...) → (accum, B/accum, ...) for the accumulation scan.

    The averaged gradient is invariant to which examples form a
    microbatch (mean of equal-sized microbatch-mean grads == global
    mean), so the split is chosen for *layout*: each of the ``n_shards``
    devices contributes the a-th sub-block of its local batch shard to
    microbatch a, making the reshape purely local — no resharding
    collective at step entry. Falls back to contiguous chunks (same
    math, one input reshard) when B doesn't divide that way.
    """
    B = x.shape[0]
    if B % accum:
        raise ValueError(
            f"global batch {B} not divisible by grad_accum {accum}"
        )
    rest = x.shape[1:]
    if B % (accum * n_shards) == 0:
        m = x.reshape(n_shards, accum, B // (accum * n_shards), *rest)
        m = jnp.moveaxis(m, 1, 0).reshape(accum, B // accum, *rest)
    else:
        m = x.reshape(accum, B // accum, *rest)
    return jax.lax.with_sharding_constraint(m, micro_sh)


def _build_step(mesh: Mesh, loss_fn: Callable, *, stage: int,
                accum: int):
    """The zero/DP step function plus its batch shardings — shared by
    the runtime path (:func:`make_zero_train_step`) and the AOT layout
    validation path (:func:`lower_zero_train_step`)."""
    if stage not in (0, 1, 3):
        raise ValueError(f"zero_stage must be 0, 1 or 3, got {stage}")
    if accum < 1:
        raise ValueError(f"accum must be >= 1, got {accum}")
    from pytorch_distributed_nn_tpu.runtime.mesh import AXIS_SEQ, data_axis_size

    # under sequence parallelism the (B, T) token batches arrive
    # seq-sharded from the loader; the jit contract must match or the
    # compiler would reshard (all-gathering the sequence) at entry
    seq = mesh.shape.get(AXIS_SEQ, 1)
    batch_spec = batch_pspec(AXIS_SEQ) if seq > 1 else batch_pspec()
    batch_sh = NamedSharding(mesh, batch_spec)
    micro_sh = NamedSharding(mesh, P(None, *batch_spec))
    n_shards = data_axis_size(mesh)

    def step(state: TrainState, x, y):
        loss, new_model_state, grads = dp._loss_and_grads(
            state, x, y, loss_fn
        )
        new_state = state.apply_gradients(grads).replace(
            model_state=new_model_state
        )
        return new_state, {"loss": loss}

    def step_accum(state: TrainState, x, y):
        mx = _split_microbatches(x, accum, n_shards, micro_sh)
        my = _split_microbatches(y, accum, n_shards, micro_sh)

        def body(carry, inp):
            model_state, gsum, lsum = carry
            i, bx, by = inp
            # decorrelate the per-microbatch dropout stream (forward
            # folds state.step on top, decorrelating across steps)
            fwd_state = state.replace(
                model_state=model_state,
                rng=jax.random.fold_in(state.rng, i),
            )
            loss, new_model_state, grads = dp._loss_and_grads(
                fwd_state, bx, by, loss_fn
            )
            gsum = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gsum, grads
            )
            return (new_model_state, gsum, lsum + loss), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params
        )
        (new_model_state, gsum, lsum), _ = jax.lax.scan(
            body,
            (state.model_state, zeros, jnp.zeros((), jnp.float32)),
            (jnp.arange(accum), mx, my),
        )
        grads = jax.tree.map(
            lambda a, p: (a / accum).astype(p.dtype), gsum, state.params
        )
        new_state = state.apply_gradients(grads).replace(
            model_state=new_model_state
        )
        return new_state, {"loss": lsum / accum}

    return (step_accum if accum > 1 else step), batch_sh


def _jit_step(step, shardings, batch_sh, mesh):
    return jax.jit(
        step,
        in_shardings=(shardings, batch_sh, batch_sh),
        out_shardings=(shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )


def make_zero_train_step(mesh: Mesh, loss_fn: Callable, *, stage: int = 3,
                         accum: int = 1):
    """Returns (step, place_state). The step body is identical to DP —
    sharded DP is purely a layout change (SURVEY.md §3.4 'expressed
    declaratively as shardings').

    ``accum > 1`` runs gradient accumulation: the global batch is split
    into ``accum`` microbatches scanned sequentially (``lax.scan``),
    per-microbatch grads summed in f32, one optimizer step on the mean.
    Peak activation memory drops ~accum×. For deterministic stateless
    models the gradient is the same global-batch mean the accum=1 step
    computes; dropout models re-draw masks per microbatch and BatchNorm
    stats update sequentially per microbatch (the same semantics as a
    torch accumulation loop), which differs slightly from one full-batch
    step.
    """
    step, batch_sh = _build_step(mesh, loss_fn, stage=stage, accum=accum)
    compiled: dict = {}

    def place_state(state: TrainState) -> TrainState:
        shardings = state_shardings(state, mesh, stage=stage)
        placed = global_device_put(state, shardings)
        compiled["step"] = _jit_step(step, shardings, batch_sh, mesh)
        return placed

    def step_dispatch(state, x, y):
        if "step" not in compiled:
            raise RuntimeError("call place_state before stepping")
        return compiled["step"](state, x, y)

    # the jit's AOT entry, so callers can read the compiled program
    # (kernel and collective counts) of whichever strategy they hold
    step_dispatch.lower = lambda *args: compiled["step"].lower(*args)
    return step_dispatch, place_state


def lower_zero_train_step(mesh: Mesh, loss_fn: Callable,
                          abstract_state: TrainState,
                          x_spec, y_spec, *, stage: int = 3,
                          accum: int = 1):
    """AOT-lower the zero train step for ABSTRACT inputs — nothing is
    materialized on any device, so arbitrarily large layouts (the true
    8B config 5) lower on a virtual topology. Returns the jax Lowered;
    callers ``.compile()`` it for the SPMD partitioner's verdict and
    per-chip memory analysis (scripts/validate_8b_layout.py)."""
    step, batch_sh = _build_step(mesh, loss_fn, stage=stage, accum=accum)
    shardings = state_shardings(abstract_state, mesh, stage=stage)
    state_arg = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_state, shardings,
    )
    def arg(spec):
        return jax.ShapeDtypeStruct(spec.shape, spec.dtype,
                                    sharding=batch_sh)

    return _jit_step(step, shardings, batch_sh, mesh).lower(
        state_arg, arg(x_spec), arg(y_spec)
    )
