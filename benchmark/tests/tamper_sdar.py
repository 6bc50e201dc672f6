"""Faults for a block decoder's timed path, by name: what
``test_harness_sdar.py`` puts into the tiny run on the CPU and
``probe_sdar_tamper.py`` into the cell on the chip. The reference
regenerates its own weights and knows nothing of them.

- ``causal_block``: the mask inside a block made causal: a query sees
  the positions up to its own, not up to its block's end (the three
  attention routines of the decode cache: a round's, whose kernel the
  chip runs, the dense one it falls back to anywhere else, and a
  blockwise prefill's).
- ``commit_skipped``: the owed write left out. A row that *owes* feeds
  its finished block's final tokens beside the open block, and that
  forward writes the finished block's keys and values (the commit,
  since PR 44 no forward of its own); here the rows ``[depth - B,
  depth)`` of a row that owes keep what they held: those of the block's
  last denoising step, computed while some of its positions were still
  fed the mask token.
- ``experts_zeroed``: every ``moe/experts_down`` zeroed.
"""

import jax
import jax.numpy as jnp

from pytorch_distributed_nn_tpu.nn import attention
from pytorch_distributed_nn_tpu.serve import engine as engine_mod

FAULTS = ("causal_block", "commit_skipped", "experts_zeroed")


def apply(fault: str, engine, setattr_) -> None:
    """Put ``fault`` into the program. ``setattr_(object, name, value)``
    is what patches a module (``monkeypatch.setattr`` in a test, plain
    ``setattr`` in a process that ends with the run)."""
    if fault == "experts_zeroed":
        def zero_down(path, leaf):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            return jnp.zeros_like(leaf) \
                if name.endswith("moe/experts_down") else leaf
        engine.params = jax.tree_util.tree_map_with_path(
            zero_down, engine.params)
    elif fault == "causal_block":
        B = engine._block["block_length"]
        dense, tiled, round_ = attention._cache_attention, \
            attention._prefill_attention, attention._round_attention
        in_round = []    # the round's routine falls back to the dense one

        def within(T):   # a fed token's place in its block (starts are
            return jnp.arange(T) % B - (B - 1)   # whole blocks)

        def causal_dense(q, k, v, pos_mask, *a, **kw):
            if in_round:   # its mask is causal already
                return dense(q, k, v, pos_mask, *a, **kw)
            seen = pos_mask.sum(axis=-1) - 1              # (B|1, T)
            own = seen + within(pos_mask.shape[1])[None]
            keys = jnp.arange(pos_mask.shape[-1])[None, None, :]
            return dense(q, k, v, pos_mask & (keys <= own[..., None]),
                         *a, **kw)

        def causal_tiled(q, k, v, positions, lengths=None):
            return tiled(q, k, v, positions + within(q.shape[1])[None],
                         lengths)

        def causal_round(q, k, v, seen, lengths, dtype):
            in_round.append(True)
            try:
                return round_(q, k, v, seen + within(q.shape[1])[None],
                              lengths, dtype)
            finally:
                in_round.pop()
        setattr_(attention, "_cache_attention", causal_dense)
        setattr_(attention, "_prefill_attention", causal_tiled)
        setattr_(attention, "_round_attention", causal_round)
    elif fault == "commit_skipped":
        sound = engine_mod._block_round
        B = engine._block["block_length"]

        def skipped(model, block, params, cache, out, place, active,
                    *rest):
            result = sound(model, block, params, cache, out, place, active,
                           *rest)
            start = (place["depth"] - B)[:, None]
            owing = (active & place["owes"])[:, None]

            def keep(old, new):     # rows by position: (slots, S, ...)
                if new.ndim < 3:
                    return new
                row = jnp.arange(new.shape[1])[None]
                owed = owing & (row >= start) & (row < start + B)
                return jnp.where(
                    owed.reshape(owed.shape + (1,) * (new.ndim - 2)),
                    old, new)
            return result[:4] + (jax.tree.map(keep, cache, result[4]),) \
                + result[5:]
        setattr_(engine_mod, "_block_round", skipped)
    else:
        raise ValueError(f"unknown fault {fault!r} (of {FAULTS})")
