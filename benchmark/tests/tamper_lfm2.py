"""Faults for LFM2's timed path, by name: what ``test_harness_lfm2.py``
puts into the tiny run on the CPU and ``probe_lfm2_tamper.py`` into the
cell on the chip. The reference regenerates its own weights, carries
nothing and knows nothing of them.

- ``tail_one_off``: every convolution layer's carried inputs one
  position off as a prefilled row joins the batch (the newest of the two
  is lost, the other moves up): the first two rounds of every request
  convolve the wrong inputs.
- ``padding_let_through``: a prefill's bucket padding let through to the
  tail (the model is told every fed position is real): the row's tail is
  that of the bucket's last two positions, not of the prompt's.
- ``experts_zeroed``: every ``moe/experts_down`` zeroed.
- ``keys_zeroed``: the attention layers' cached keys zeroed as a
  prefilled row joins the batch: the rounds' queries score every prompt
  position alike.
"""

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu.serve import engine as engine_mod

FAULTS = ("tail_one_off", "padding_let_through", "experts_zeroed",
          "keys_zeroed")


def _on_leaf(tree, leaf: str, f):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: f(x) if getattr(path[-1], "key", "") == leaf else x,
        tree)


def apply(fault: str, engine, setattr_) -> None:
    """Put ``fault`` into the program. ``setattr_(object, name, value)``
    is what patches a module (``monkeypatch.setattr`` in a test, plain
    ``setattr`` in a process that ends with the run)."""
    insert, mask_kw = engine_mod._insert_row, engine_mod._mask_kw
    if fault == "experts_zeroed":
        def zero_down(path, leaf):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            return jnp.zeros_like(leaf) \
                if name.endswith("moe/experts_down") else leaf
        engine.params = jax.tree_util.tree_map_with_path(
            zero_down, engine.params)
    elif fault == "tail_one_off":
        # (rolled on the host: the chip's compiler aborts on ``jnp.roll``
        # of a ``(1, 2, 2048)`` bf16 leaf, PERF.md sec. 7, PR 46)
        setattr_(engine_mod, "_insert_row",
                 lambda batch, row, slot, **kw: insert(
                     batch, _on_leaf(row, "conv_tail", lambda x: jnp.asarray(
                         np.roll(np.asarray(x), 1, axis=1))),
                     slot, **kw))
    elif fault == "keys_zeroed":
        setattr_(engine_mod, "_insert_row",
                 lambda batch, row, slot, **kw: insert(
                     batch, _on_leaf(row, "cached_key", jnp.zeros_like),
                     slot, **kw))
    elif fault == "padding_let_through":
        setattr_(engine_mod, "_mask_kw", lambda model, mask: mask_kw(
            model, jnp.ones_like(mask) if mask.shape[1] > 1 else mask))
    else:
        raise ValueError(f"unknown fault {fault!r} (of {FAULTS})")
