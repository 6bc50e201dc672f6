"""A served prefill's head scores the row the engine reads (ISSUE 51).

Every model the engine can serve takes ``head_rows`` (B, K) int32: the
rows of a sequence that reach the final norm and the head
(``nn.head_input``). ``serve/engine._apply_prefill_at`` passes each
row's last real position, so no ``_serve_prefill`` program multiplies
``(bucket, d) x (d, vocab)`` for the one row it reads. Held here, a case
a served family, at small float32 sizes on the CPU: the model's
``head_rows`` against a gather behind the whole logits; the engine's
prefill against the formula it replaced (whole logits, then the row),
for a miss and for a suffix behind restored rows; and the lowered
program's text, which holds no float32 value of ``bucket x vocab``.
"""

import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import serve_program_digests  # noqa: E402

from pytorch_distributed_nn_tpu.config import ModelConfig  # noqa: E402
from pytorch_distributed_nn_tpu.inference.generate import (  # noqa: E402
    init_cache,
)
from pytorch_distributed_nn_tpu.models import get_model  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine  # noqa: E402

# a prime no width of any of these models is, so that a value of
# ``bucket x vocab`` in a program's text is the logits and nothing else
V = 251
BUCKET, ROW = 16, 32
_SHAPES = serve_program_digests._SHAPES
_LLAMA = dict(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
              mlp_dim=64, rope_theta=1e6)
FAMILIES = {
    "llama": ("llama3_8b", _LLAMA),
    # the int8 head: its scales are the kernel's columns', not the rows'
    "llama_int8": ("llama3_8b", dict(_LLAMA, quantized=True)),
    "longcat": _SHAPES["longcat"],
    "k_exaone": _SHAPES["kexaone"],
    "ax_k1": ("ax_k1", dict(
        num_layers=3, d_model=64, num_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, mlp_dim=192, expert_mlp_dim=32, num_experts=16,
        moe_topk=4, n_group=4, topk_group=2, rope_original_positions=64,
        ep_size=2, ep_rank=0)),
    "jamba": ("jamba", dict(
        num_layers=4, d_model=64, num_heads=4, num_kv_heads=1, mlp_dim=128,
        attn_layer_period=2, attn_layer_offset=1, mamba_dt_rank=8)),
    "lfm2": ("lfm2_8b_a1b", dict(
        d_model=64, num_heads=4, num_kv_heads=2, mlp_dim=128,
        expert_mlp_dim=32, num_experts=8, moe_topk=2, num_dense_layers=2,
        num_layers=4,
        layer_types=("conv", "conv", "full_attention", "conv"))),
    "transformer_lm": ("transformer_lm", dict(
        num_layers=2, d_model=32, num_heads=4, mlp_dim=64, max_len=64)),
    "brumby": _SHAPES["brumby"],
    # a block decoder: its prefill yields no token and skips the head
    "sdar": _SHAPES["sdar"],
}


@pytest.fixture(scope="module", params=list(FAMILIES))
def served(request):
    """(model, params) of one family, its own initialisers' draw."""
    name, extra = FAMILIES[request.param]
    model = get_model(ModelConfig(
        name=name, dtype="float32", compute_dtype="float32",
        extra=dict(extra, vocab_size=V)))
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    return model, params


def _tokens(batch: int, seed: int):
    return jax.random.randint(jax.random.key(seed), (batch, BUCKET), 0,
                              V - 1, jnp.int32)


def _whole_logits_then_the_row(model, params, cache, tokens, lengths,
                               starts):
    """``_apply_prefill_at`` as it was before ISSUE 51 for a model
    without ``head_rows``: the logits of every fed position, then each
    row's last real one."""
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens, train=False,
        decode=True, mutable=["cache"],
        cache_positions=starts.astype(jnp.int32),
        **engine._mask_kw(model, jnp.arange(tokens.shape[1])[None, :]
                          < lengths[:, None]))
    last = (lengths.astype(jnp.int32) - 1)[:, None, None]
    return jnp.take_along_axis(logits, last, axis=1)[:, 0, :], \
        mutated["cache"]


_old = jax.jit(_whole_logits_then_the_row, static_argnums=(0,))
_prefill = jax.jit(engine._apply_prefill_at, static_argnums=(0,))


def _same_cache(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6)


def test_head_rows_is_a_gather_behind_the_whole_logits(served):
    """``model.apply(..., head_rows=r)`` is ``take_along_axis`` of the
    whole logits at ``r``, for ragged lengths in one batch, under
    ``token_mask`` where the model takes one, and for ``K > 1`` rows a
    sequence; the cache it leaves is the same."""
    model, params = served
    tokens = _tokens(3, 1)
    lengths = jnp.asarray([BUCKET, 9, 4])
    rows = jnp.stack([lengths - 1, lengths // 2, jnp.zeros_like(lengths)],
                     axis=1).astype(jnp.int32)
    cache = init_cache(model, 3, ROW)

    @jax.jit
    def apply(head_rows=None):
        return model.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            decode=True, mutable=["cache"],
            cache_positions=jnp.zeros((3,), jnp.int32), head_rows=head_rows,
            **engine._mask_kw(model, jnp.arange(BUCKET)[None, :]
                              < lengths[:, None]))

    whole, cache_whole = apply()
    got, cache_got = apply(rows)
    want = jnp.take_along_axis(whole, rows[..., None], axis=1)
    assert got.shape == (3, 3, V) and got.dtype == whole.dtype
    assert float(jnp.abs(want).mean()) > 1e-3
    assert float(jnp.abs(got - want).max()) < 1e-5
    _same_cache(cache_got["cache"], cache_whole["cache"])


def test_engine_prefill_is_the_whole_logits_formula(served):
    """``_apply_prefill_at``'s ``(B, V)`` logits and ``_serve_prefill``'s
    first token are what the whole logits and then the row gave: for a
    miss and for a suffix behind rows (or state) already in the cache,
    ragged in one batch."""
    model, params = served
    for batch in (2, 1):
        # a miss: rows of one bucket, 11 and 4 real tokens
        cases = {"miss": (
            init_cache(model, batch, ROW), _tokens(batch, 2),
            jnp.asarray([11, 4][:batch]), jnp.zeros((batch,), jnp.int32))}
        # a suffix: 8 positions already there (two whole blocks of a
        # block decoder), then 5 and 3 real tokens from position 8
        _, behind = _old(model, params, init_cache(model, batch, ROW),
                        _tokens(batch, 3), jnp.full((batch,), 8),
                        jnp.zeros((batch,), jnp.int32))
        cases["suffix"] = (behind, _tokens(batch, 4),
                           jnp.asarray([5, 3][:batch]),
                           jnp.full((batch,), 8, jnp.int32))
        for name, (cache, *fed) in cases.items():
            want, want_cache = _old(model, params, cache, *fed)
            assert float(jnp.abs(want).mean()) > 1e-3, name
            if batch == 2:
                got, got_cache = _prefill(model, params, cache, *fed)
                assert got.shape == (2, V), name
                assert float(jnp.abs(got - want).max()) < 1e-5, name
                _same_cache(got_cache, want_cache)
                continue
            # the program the engine runs: one row, its first token
            tok, got_cache, _ = engine._serve_prefill(
                model, params, jax.tree.map(jnp.copy, cache), *fed)
            _same_cache(got_cache, want_cache)
            if engine._block_of(model) is None:
                assert int(tok[0]) == int(jnp.argmax(want[0])), name
            else:
                assert tok is None


def test_no_serve_prefill_holds_the_buckets_logits(served):
    """The lowered ``_serve_prefill`` holds no float32 value of
    ``bucket x vocab``; the formula it replaced does, which is how the
    pattern is known to find one."""
    model, params = served
    shapes = jax.eval_shape(lambda: params)
    cache = jax.eval_shape(lambda: init_cache(model, 1, BUCKET))
    args = (jax.ShapeDtypeStruct((1, BUCKET), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32))
    logits = re.compile(
        rf"(?:1x)?{BUCKET}x{V}xf32|f32\[(?:1,)?{BUCKET},{V}\]")
    text = engine._serve_prefill.lower(model, shapes, cache, *args).as_text()
    assert not logits.findall(text)
    if engine._block_of(model) is None:
        assert re.search(rf"tensor<1x{V}xf32>", text)   # the row it reads
    witness = _old.lower(model, shapes, cache, *args).as_text()
    assert logits.findall(witness)


@pytest.mark.parametrize("program", serve_program_digests._LEFT_ALONE)
def test_the_two_families_that_had_head_rows_lower_as_before(program):
    """Brumby's prefill was told its row already and SDAR's skips the
    head; SDAR's round (``_block_round``, under the step's name) passes
    its open block's rows itself. Their serve programs lower to the text
    of the commit before ISSUE 51, byte for byte
    (``tests/serve_program_digests.py`` says how the file was made)."""
    pinned = json.loads((Path(__file__).resolve().parent / "data"
                         / "serve_program_digests.json").read_text())
    assert serve_program_digests.digest(program) == pinned[program]
