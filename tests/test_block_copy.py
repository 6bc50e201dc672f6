"""The copy between a batch row and the block store (ISSUE 47).

``_save_blocks`` and ``_restore_blocks`` against a plain NumPy copy, a
block at a time, over the leaf shapes the served models keep; that
neither program holds a loop over the blocks; and the counter the
engine keeps of the blocks it copied.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.serve import ServingEngine
from pytorch_distributed_nn_tpu.serve import engine as engine_mod

BS = 16
SLOTS, SLOT = 3, 1
# tails of the cache's leaves (slots, S, ...) by what keeps such a cache,
# the row length S, and the store's dtype where it is not the cache's
LEAVES = {
    "flat": dict(tails=[(256,), (256,)]),                 # (slots, S, H*D)
    "heads": dict(tails=[(2, 128), (2, 128)]),            # (slots, S, H, D)
    "latent": dict(tails=[(512,), (64,)]),                # latent + rotated
    "scalar": dict(tails=[(32,), ()]),                    # a shared index
    "other_dtype": dict(tails=[(32,), (4, 8)], store=jnp.bfloat16),
    "ragged": dict(tails=[(32,)], S=72),                  # S % BS != 0
}


def _trees(kind, seed=0):
    spec = LEAVES[kind]
    S = spec.get("S", 64)
    rng = np.random.default_rng(seed)
    cache, store, row = {}, {}, {}
    for i, tail in enumerate(spec["tails"]):
        name = f"leaf{i}"
        if tail == ():
            cache[name] = jnp.asarray(7, jnp.int32)
            store[name] = jnp.asarray(11, jnp.int32)
            row[name] = jnp.asarray(13, jnp.int32)
            continue
        draw = lambda *shape: jnp.asarray(  # noqa: E731
            rng.standard_normal(shape + tail), jnp.float32)
        cache[name] = draw(SLOTS, S)
        store[name] = draw(SLOTS * (S // BS) + 2, BS).astype(
            spec.get("store", jnp.float32))
        row[name] = draw(1, S)
    return cache, store, row, S


def _table(S, n, width, seed=1):
    """``n`` distinct physical blocks, none of them 0, then zeros: the
    padded table ``_donate_blocks`` and ``_prefill_into`` build."""
    blocks = SLOTS * (S // BS) + 2
    table = np.zeros((width,), np.int32)
    table[:n] = np.random.default_rng(seed).permutation(
        np.arange(1, blocks))[:n]
    return table


def _cases():
    for kind, spec in LEAVES.items():
        full = spec.get("S", 64) // BS
        for n in (0, 1, full - 1, full):
            yield kind, n


@pytest.mark.parametrize("direction", ["save", "restore"])
@pytest.mark.parametrize("kind,n", list(_cases()))
def test_copy_is_the_plain_copy_a_block_at_a_time(kind, n, direction):
    cache, store, row, S = _trees(kind)
    # the engine's table is ceil(max_seq_len / block_size) wide
    table = _table(S, n, -(-S // BS))
    before = jax.tree.map(np.asarray, store)
    if direction == "save":
        got = engine_mod._save_blocks(
            cache, jax.tree.map(jnp.copy, store), BS,
            np.int32(SLOT), table, np.int32(n))
        for name, c in cache.items():
            want = before[name].copy()
            for j in range(n if c.ndim else 0):
                want[table[j]] = np.asarray(
                    c[SLOT, j * BS:(j + 1) * BS].astype(want.dtype))
            # every block outside table[:n] as it was, block 0 among
            # them though the table's tail names it; the store's dtype
            assert got[name].dtype == store[name].dtype
            np.testing.assert_array_equal(np.asarray(got[name]), want)
        return

    def restored(m):
        return jax.tree.map(np.asarray, engine_mod._restore_blocks(
            jax.tree.map(jnp.copy, row), store, BS, table, np.int32(m)))

    def want(m):
        out = {name: np.array(r) for name, r in row.items()}
        for name, r in row.items():
            for j in range(m if r.ndim else 0):
                out[name][0, j * BS:(j + 1) * BS] = \
                    before[name][table[j]].astype(out[name].dtype)
        return out

    # rows at and past m * BS keep the row cache's values; one block
    # fewer leaves exactly the last block unrestored (what the A.X-K1
    # tamper tests rely on)
    for m in (n, n - 1) if n else (n,):
        for name, leaf in restored(m).items():
            assert leaf.dtype == row[name].dtype
            np.testing.assert_array_equal(leaf, want(m)[name])


def test_neither_program_loops_over_the_blocks():
    """The lowered text of both programs for a two-leaf cache holds no
    ``while``: a copy costs its bytes, whatever its block count."""
    cache, store, row, S = _trees("latent")
    table = _table(S, 3, S // BS)
    save = engine_mod._save_blocks.lower(
        cache, store, BS, np.int32(SLOT), table, np.int32(3)).as_text()
    restore = engine_mod._restore_blocks.lower(
        row, store, BS, table, np.int32(3)).as_text()
    for text, op in ((save, "scatter"), (restore, "gather")):
        assert "while" not in text
        assert text.count(f'"stablehlo.{op}"(') == 2  # one a leaf


def test_engine_counts_the_blocks_it_copies(tiny_llama):
    """``serve_store_blocks_copied_total``: ``depth // block_size`` a
    retire under ``save``, the hit's blocks an admission under
    ``restore``."""
    obs.reset_registry()
    model, params = tiny_llama
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=64)
    bs = eng.scheduler.pool.block_size
    prompt = np.random.default_rng(3).integers(
        1, 97, size=(37,)).astype(np.int32)
    counter = obs.get_registry().counter(
        "serve_store_blocks_copied_total", labels=("direction",))
    eng.submit(prompt, 4)
    eng.run_until_idle()
    depth = len(prompt) + 4 - 1
    assert counter.value(direction="save") == depth // bs
    assert counter.value(direction="restore") == 0
    eng.submit(prompt, 2)
    eng.run_until_idle()
    hit = eng.completed[-1]["cached_tokens"] // bs
    assert hit == (len(prompt) - 1) // bs
    assert counter.value(direction="restore") == hit
    assert counter.value(direction="save") == \
        depth // bs + (len(prompt) + 2 - 1) // bs
