"""Multi-step dispatch: fuse k train steps into ONE device program.

The reference's training loop is one `optimizer.step()` per Python
iteration — fine when each step is milliseconds of GPU work. On TPU the
idiomatic loop hoists the iteration itself onto the device: `lax.scan`
over a leading-axis-stacked batch pool runs k optimizer steps per
dispatch, so the host's per-dispatch latency amortizes k-fold. For
dispatch-bound workloads (`mlp_mnist`) this IS the throughput.

Semantics: identical math to k sequential `step_fn` calls on the same
batches — the scan threads the TrainState through in order, and the
returned metrics are the last step's (matching what a Python loop
would hold after k iterations). Metrics for ALL k steps come back
stacked under the ``"all"`` key so logging can still see every step.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def make_multistep(step_fn: Callable, k: int) -> Callable:
    """Wrap a ``step(state, x, y) -> (state, metrics)`` into
    ``multistep(state, xs, ys) -> (state, metrics)`` running ``k``
    fused steps. ``xs``/``ys`` carry a leading POOL axis of any length
    P <= k: step i trains on slice ``i % P`` (the same cycling a host
    loop over a batch pool does), so a small device-resident pool need
    not be duplicated k times in HBM — the scan runs over step indices
    and dynamically indexes the pool.

    ``step_fn`` may already be jitted (inner jit inlines into the outer
    trace). The state is donated: k steps in flight never hold two
    copies of the optimizer state.
    """
    if k < 1:
        raise ValueError(f"multistep k must be >= 1, got {k}")

    def multistep(state, xs, ys):
        pool = jax.tree.leaves(xs)[0].shape[0]
        if pool > k:
            raise ValueError(
                f"batch pool ({pool}) larger than step count ({k}): "
                f"{pool - k} batches would silently never train"
            )

        def body(s, i):
            x = jax.tree.map(lambda a: a[i % pool], xs)
            y = jax.tree.map(lambda a: a[i % pool], ys)
            s, m = step_fn(s, x, y)
            return s, m

        state, ms = jax.lax.scan(body, state, jnp.arange(k))
        last = jax.tree.map(lambda a: a[-1], ms)
        last["all"] = ms
        return state, last

    return jax.jit(multistep, donate_argnums=(0,))
