"""TrainState: the pytree the whole framework threads through steps.

Replaces the reference's implicit (model, optimizer) object pair —
everything a step touches (params, mutable model state like BatchNorm
stats, optimizer state, step counter) lives in one immutable pytree so it
can be sharded, donated, and checkpointed uniformly.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    model_state: Any  # e.g. BatchNorm running stats ({} if none)
    opt_state: Any
    rng: jax.Array  # base key for per-step stochastic ops (dropout)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    apply_fn: Callable = struct.field(pytree_node=False)

    def apply_gradients(self, grads) -> "TrainState":
        with jax.named_scope("optimizer"):   # metadata, obs/scopes.py
            updates, new_opt_state = self.tx.update(
                grads, self.opt_state, self.params
            )
            return self.replace(
                step=self.step + 1,
                params=optax.apply_updates(self.params, updates),
                opt_state=new_opt_state,
            )

    @classmethod
    def create(cls, *, apply_fn, params, tx, model_state=None,
               rng=None) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            model_state={} if model_state is None else model_state,
            opt_state=tx.init(params),
            rng=jax.random.key(0) if rng is None else rng,
            tx=tx,
            apply_fn=apply_fn,
        )


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def param_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
