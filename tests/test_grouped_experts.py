"""The held experts' grouped product (``ops/pallas/grouped_experts``):
the Pallas kernel in interpret mode against the plain loop over the same
sorted tiles and against a dense sum over every expert in float32, the
layer's counters against the unrolled form's, and the rule by which
``parallel/expert.HeldExpertsMoE`` sends a layer to one form or the
other. The dispatcher asks for the backend and these tests answer for
the chip; the kernel compiled for the chip at SDAR's widths is in
``tests/test_chip_compile.py`` (no chip), and on the chip
``scripts/sweep_grouped_experts.py`` makes the same comparison at the
cell's size.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.ops.pallas import grouped_experts as kernel
from pytorch_distributed_nn_tpu.parallel import expert
from pytorch_distributed_nn_tpu.parallel.expert import HeldExpertsMoE

COUNTER = "held_experts_calls_total"
D, FF, ROUTED, TOPK = 128, 256, 32, 4


@pytest.fixture
def on_chip(monkeypatch):
    """The dispatcher sees a TPU, and the kernel runs interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "_pallas", functools.partial(
        kernel._pallas, interpret=True))
    obs.reset_registry()
    yield
    obs.reset_registry()


def _calls(execution):
    return obs.get_registry().counter(COUNTER, labels=("execution",)).value(
        execution=execution)


def _layer(dtype=jnp.bfloat16, **fields):
    return HeldExpertsMoE(**{**dict(
        num_experts=ROUTED, mlp_dim=FF, k=TOPK, scoring="softmax",
        renormalize=True, dtype=dtype, param_dtype=dtype), **fields})


def _variables(layer, bias=None):
    """Parameters drawn at the layer's own initialiser, and a selection
    bias that sends every token to the experts it names."""
    params = layer.init(jax.random.key(3), jnp.zeros((1, 4, D), layer.dtype))
    variables = {"params": params["params"]}
    if bias:
        b = np.zeros((layer.num_experts + layer.num_zero_experts,),
                     np.float32)
        b[list(bias)] = 10.0
        variables["buffers"] = {"selection_bias": jnp.asarray(b)}
    return variables


def _dense(layer, variables, x, token_mask):
    """The layer's definition, every held expert on every token, in
    float32 on the operands as the layer rounds them."""
    p = variables["params"]
    held = layer.num_experts // layer.ep_size
    first = layer.ep_rank * held
    a = x.reshape(-1, D)
    f32 = lambda v: v.astype(layer.dtype).astype(jnp.float32)  # noqa: E731
    logits = jnp.dot(a.astype(jnp.float32),
                     p["router"]["kernel"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1) \
        if layer.scoring == "softmax" else jax.nn.sigmoid(logits)
    choose = probs + variables.get("buffers", {}).get("selection_bias", 0.0)
    _, idx = jax.lax.top_k(choose, layer.k)
    w = jnp.take_along_axis(probs, idx, axis=1)
    if layer.renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * layer.routed_scaling
    if token_mask is not None:
        w = jnp.where(token_mask.reshape(-1, 1), w, 0.0)
    out = jnp.zeros(a.shape, jnp.float32)
    for j in range(held):
        wg, wu = (f32(p[n][:, j * FF:(j + 1) * FF])
                  for n in ("experts_gate", "experts_up"))
        wd = f32(p["experts_down"][:, j * D:(j + 1) * D])
        h = jax.nn.silu(f32(a) @ wg) * (f32(a) @ wu)
        mine = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=1)
        out = out + (f32(h) @ wd) * mine[:, None]
    return out.reshape(x.shape)


def _mask(B, T, lengths):
    return jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None]


# name -> (layer fields, (B, T), experts every token is sent to, the
# real tokens a row, what the counters must read): the cases ISSUE 43
# names, each through the layer as a serve program calls it
CASES = {
    "every_expert_touched": (
        {}, (4, 32), None, None, lambda s, held: s[3] == held),
    "most_untouched": (
        {}, (4, 16), (1, 5, 17, 30), None, lambda s, held: s[3] == 4),
    "one_expert_over_two_tiles": (
        {}, (2, 24), (7,), None, lambda s, held: s[3] > 1),
    "token_mask_hides_rows": (
        {}, (4, 16), None, (16, 3, 0, 9),
        lambda s, held: s[0] == 28 * TOPK),
    "under_one_tile": (
        {}, (1, 3), None, None, lambda s, held: s[2] == 3 * TOPK),
    "ep_size_2_rank_1": (
        dict(num_experts=2 * ROUTED, ep_size=2, ep_rank=1), (4, 16), None,
        None, lambda s, held: 0 < s[2] < s[0]),
    "no_renormalize_scaled": (
        dict(renormalize=False, routed_scaling=2.5), (4, 16), None, None,
        lambda s, held: s[2] == s[0]),
    "sigmoid_renormalize": (
        dict(scoring="sigmoid"), (4, 16), None, None,
        lambda s, held: s[2] == s[0]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_equals_the_plain_loop_and_the_dense_sum(
        name, on_chip, monkeypatch):
    fields, (B, T), bias, lengths, counters_hold = CASES[name]
    layer = _layer(**fields)
    held = layer.num_experts // layer.ep_size
    assert expert.experts_grouped(held)
    variables = _variables(layer, bias)
    x = jax.random.normal(jax.random.key(11), (B, T, D)).astype(layer.dtype)
    token_mask = None if lengths is None else _mask(B, T, lengths)

    by_kernel, stats = layer.apply(variables, x, token_mask=token_mask)
    assert _calls("grouped_kernel") == 1 and _calls("grouped_loop") == 0
    assert counters_hold([int(v) for v in stats], held), stats
    if name == "one_expert_over_two_tiles":
        tm, _ = kernel.tiles(B * T, TOPK, layer.num_experts, D, FF)
        assert B * T > tm   # expert 7 has every token: three tiles

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    by_loop, stats_loop = layer.apply(variables, x, token_mask=token_mask)
    assert _calls("grouped_loop") == 1
    np.testing.assert_array_equal(stats, stats_loop)
    # the same products on the same tiles: what differs is the order a
    # product adds its terms in
    np.testing.assert_allclose(by_kernel.astype(jnp.float32),
                               by_loop.astype(jnp.float32),
                               rtol=2e-2, atol=2e-3)

    # the unrolled form on the same input: the same counters
    monkeypatch.setattr(expert, "GROUPED_FROM", held + 1)
    unrolled, stats_unrolled = layer.apply(variables, x,
                                           token_mask=token_mask)
    assert _calls("unrolled_loop") == 1
    np.testing.assert_array_equal(stats, stats_unrolled)

    want = _dense(layer, variables, x, token_mask)
    size = float(jnp.abs(want).max())
    for got in (by_kernel, by_loop, unrolled):
        gap = float(jnp.abs(got.astype(jnp.float32) - want).max())
        assert gap <= 2e-2 * size + 1e-6, (gap, size)
    if lengths is not None:     # a hidden token reaches no expert
        hidden = ~np.asarray(token_mask)
        assert not np.asarray(by_kernel.astype(jnp.float32))[hidden].any()


def test_float32_layer_runs_the_plain_loop_on_a_tpu_too(on_chip):
    """The kernel reads bf16 blocks as they lie; a float32 layer is the
    loop's, and exact against the dense sum to float32's rounding."""
    layer = _layer(dtype=jnp.float32)
    variables = _variables(layer)
    x = jax.random.normal(jax.random.key(5), (2, 16, D))
    got, _ = layer.apply(variables, x)
    assert _calls("grouped_loop") == 1 and _calls("grouped_kernel") == 0
    np.testing.assert_allclose(got, _dense(layer, variables, x, None),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fc", [128, 256])
def test_kernel_in_chunks_of_columns(fc):
    """``ff`` in two chunks a tile (the float32 sum carried in scratch)
    and in one, against the loop on the same layout; tiles past the
    live ones are not the kernel's to write."""
    held, tm, N, k = 8, 16, 40, 2
    ks = jax.random.split(jax.random.key(2), 5)
    a = jax.random.normal(ks[0], (N, D)).astype(jnp.bfloat16)
    pick = jax.random.randint(ks[1], (N, k), 0, held + 1)   # held: away
    pick = pick.at[:, 0].set(jnp.where(jnp.arange(N) < 20, 2, pick[:, 0]))
    counts = jnp.sum(pick.reshape(-1, 1) == jnp.arange(held), axis=0)
    w = [0.1 * jax.random.normal(k_, s).astype(jnp.bfloat16)
         for k_, s in zip(ks[2:], ((D, held * FF), (D, held * FF),
                                   (FF, held * D)))]
    bound = kernel.tile_bound(N * k, tm, held)
    tile_expert, live, pair_of_row, row_of_pair = kernel.layout(
        pick.reshape(-1).astype(jnp.int32), counts.astype(jnp.int32), tm,
        bound)
    assert int(counts[2]) > tm and int(live) < bound
    xs = a[jnp.minimum(pair_of_row, N * k - 1) // k]
    got = kernel._pallas(tile_expert, live, xs, *w, tm=tm, fc=fc,
                         interpret=True)
    want = kernel._loop(tile_expert, live, xs, *w, tm=tm, fc=fc)
    rows = int(live) * tm
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=2e-2,
                               atol=2e-3)
    # every computed pair lies in a live row, in its expert's tiles
    placed = np.asarray(row_of_pair)
    here = np.asarray(pick.reshape(-1)) < held
    assert (placed[here] < rows).all() and (placed[~here] == bound * tm).all()
    np.testing.assert_array_equal(
        np.asarray(pair_of_row)[placed[here]], np.flatnonzero(here))
    np.testing.assert_array_equal(
        np.asarray(tile_expert)[placed[here] // tm],
        np.asarray(pick.reshape(-1))[here])


def test_the_rule_is_on_held_alone():
    """No field says which form a layer runs: ``rolled`` is gone, and
    the layers that hold 8, 12 or 16 keep the loop an expert."""
    assert "rolled" not in {f.name for f in
                            dataclasses.fields(HeldExpertsMoE)}
    assert not any(expert.experts_grouped(h) for h in (1, 8, 12, 16))
    assert expert.experts_grouped(128)


@pytest.mark.parametrize("N,k,experts,tm", [
    (256, 8, 128, 32),      # SDAR's round: 16 rows an expert
    (512, 8, 128, 32), (1024, 8, 128, 64), (64, 8, 128, 32),
    (4096, 8, 128, 128)])
def test_tiles_follow_the_mean_rows_an_expert(N, k, experts, tm):
    got, fc = kernel.tiles(N, k, experts, 2048, 768)
    assert (got, fc) == (tm, 768)
    assert kernel.kernel_tiles(2048, 768, fc, jnp.bfloat16, jnp.bfloat16)
    assert not kernel.kernel_tiles(2048, 768, fc, jnp.float32, jnp.float32)
