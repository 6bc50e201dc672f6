#!/usr/bin/env python
"""Time one layer of held experts on the chip, a form a line (chip only;
``PERF.md`` sec. 6 holds the tables this wrote):

    python scripts/sweep_grouped_experts.py [tiles] [held] [parent]

``parallel/expert.HeldExpertsMoE`` at SDAR-30B-A3B's widths (d 2048,
expert width 768, 128 routed experts, 8 picks, softmax renormalised,
bf16), the router and the pairs' sum included, milliseconds a call and
microseconds a touched expert, on a round's 256 positions (64 rows of
4, 52 of them live; the positions of a row are one vector and a little
noise, so they pick alike as a served request's do) and on a prefill's
512 and 1,024 (one row, 300 and 1,000 real).

``tiles``: ``ops/pallas/grouped_experts`` at each ``(tm, fc)`` tried,
against the plain loop on the same tiles (the largest difference of the
layer's output over its size), the kernel alone beside the whole layer.
``held``: 16, 32, 64 and 128 of the 128 experts held here (``ep_size``
8, 4, 2, 1: a rank's share), the unrolled loop against the kernel: where
``experts_grouped``'s boundary lies. ``parent``: the rolled loop this
took the place of (one ``fori_loop`` over the held experts, each block
taken by ``dynamic_slice``), rebuilt here from the parent commit's text.
The floor beside them: the touched experts' bytes at the chip's 819 GB/s.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from pytorch_distributed_nn_tpu.ops.pallas import grouped_experts as kernel
from pytorch_distributed_nn_tpu.parallel import expert
from pytorch_distributed_nn_tpu.parallel.expert import HeldExpertsMoE
from pytorch_distributed_nn_tpu.runtime.device import configure_compile_cache

D, FF, ROUTED, TOPK = 2048, 768, 128, 8
HBM = 819e9
# name -> (rows, positions a row, live rows or real positions of one)
CALLS = {"round_256": (64, 4, 52), "prefill_512": (1, 512, 300),
         "prefill_1024": (1, 1024, 1000)}
TILES = tuple((tm, fc) for tm in (16, 32, 64, 128) for fc in (768,)) \
    + ((32, 384), (32, 256), (64, 256))


def timed(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def operands(call, held=ROUTED):
    B, T, live = CALLS[call]
    ks = jax.random.split(jax.random.key(B * T), 6)
    base = jax.random.normal(ks[0], (B, 1 if B > 1 else T, D))
    x = (base + 0.3 * jax.random.normal(ks[1], (B, T, D))) \
        .astype(jnp.bfloat16)
    mask = (jnp.arange(B) < live)[:, None] & jnp.ones((B, T), bool) \
        if B > 1 else (jnp.arange(T) < live)[None, :]
    draw = lambda k, shape: (jax.random.normal(k, shape)  # noqa: E731
                             * shape[0] ** -0.5).astype(jnp.bfloat16)
    params = {"router": {"kernel": draw(ks[2], (D, ROUTED))},
              "experts_gate": draw(ks[3], (D, held * FF)),
              "experts_up": draw(ks[4], (D, held * FF)),
              "experts_down": draw(ks[5], (FF, held * D))}
    return x, mask, params


def layer(held=ROUTED):
    return HeldExpertsMoE(
        num_experts=ROUTED, mlp_dim=FF, k=TOPK, scoring="softmax",
        renormalize=True, ep_size=ROUTED // held, ep_rank=0,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def layer_row(call, held, name):
    """The layer as ``experts_grouped`` and ``kernel.tiles`` stand."""
    x, mask, params = operands(call, held)
    run = jax.jit(lambda p, x, m: layer(held).apply({"params": p}, x,
                                                    token_mask=m))
    t = time.perf_counter()
    y, stats = jax.block_until_ready(run(params, x, mask))
    first = time.perf_counter() - t
    ms = 1e3 * timed(run, params, x, mask)
    touched = int(stats[3])
    return dict(call=call, held=held, form=name, layer_ms=round(ms, 3),
                pairs=int(stats[2]), touched=touched,
                us_a_touched_expert=round(1e3 * ms / max(touched, 1), 2),
                floor_ms=round(1e3 * touched * 3 * D * FF * 2 / HBM, 3),
                first_call_s=round(first, 1)), y


def tiles_rows(call):
    """Every ``(tm, fc)``: the whole layer against the plain loop, and
    the kernel alone on uniform picks."""
    x, mask, params = operands(call)
    N = x.shape[0] * x.shape[1]
    want = None
    for tm, fc in TILES:
        kernel.tiles = lambda *a, tm=tm, fc=fc, **k: (tm, fc)
        kernel._log_execution.cache_clear()
        row, y = layer_row(call, ROUTED, f"kernel {tm}x{fc}")
        if want is None:
            real = jax.default_backend
            jax.default_backend = lambda: "cpu"     # the plain loop
            try:
                loop, want = layer_row(call, ROUTED, f"loop {tm}")
            finally:
                jax.default_backend = real
            yield loop
        size = float(jnp.abs(want.astype(jnp.float32)).max())
        row["gap_over_size"] = float(jnp.abs(
            y.astype(jnp.float32) - want.astype(jnp.float32)).max()) / size
        # the kernel alone, on uniform picks (every expert touched)
        bound = kernel.tile_bound(N * TOPK, tm, ROUTED)
        ks = jax.random.split(jax.random.key(1), 2)
        pick = jax.random.randint(ks[0], (N * TOPK,), 0, ROUTED)
        counts = jnp.sum(pick[:, None] == jnp.arange(ROUTED), axis=0) \
            .astype(jnp.int32)
        tile_expert, live, pair_of_row, _ = kernel.layout(
            pick.astype(jnp.int32), counts, tm, bound)
        xs = x.reshape(N, D)[jnp.minimum(pair_of_row, N * TOPK - 1) // TOPK]
        row["kernel_alone_ms"] = round(1e3 * timed(
            lambda *a: kernel._pallas(*a, tm=tm, fc=fc), tile_expert, live,
            xs, params["experts_gate"], params["experts_up"],
            params["experts_down"]), 3)
        row["kernel_alone_tiles"] = [int(live), bound]
        yield row


def parent_rolled(call):
    """The parent's form: one loop over the held experts in the program,
    expert j's blocks taken by ``dynamic_slice`` (``parallel/expert.py``
    at PR 42, ``rolled=True``), inside the layer as it stands."""
    def rolled(a, out, member, w_held, counts, w_gate, w_up, w_down, *,
               token_block, dtype):
        (N, d), held, ff = a.shape, counts.shape[0], w_down.shape[0]
        tb = min(token_block, N)
        order = jnp.argsort(~member, axis=0, stable=True).astype(jnp.int32)
        order = jnp.pad(order, ((0, -N % tb), (0, 0)))
        cols = lambda w, j, width: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, j * width, width, axis=1)

        def run_expert(j, out):
            def one_block(b, out):
                mine = jax.lax.dynamic_index_in_dim(order, j, 1,
                                                    keepdims=False)
                rows = jax.lax.dynamic_slice(mine, (b * tb,), (tb,))
                live = b * tb + jnp.arange(tb) < counts[j]
                xb = a[rows]
                h = jax.nn.silu(xb @ cols(w_gate, j, ff)) \
                    * (xb @ cols(w_up, j, ff))
                yb = jnp.dot(h, cols(w_down, j, d),
                             preferred_element_type=jnp.float32)
                wt = jnp.where(live, w_held[rows, j], 0.0)
                return out.at[rows].add(yb * wt[:, None])
            return jax.lax.fori_loop(0, (counts[j] + tb - 1) // tb,
                                     one_block, out)
        return jax.lax.fori_loop(0, held, run_expert, out)

    unrolled, boundary = expert._unrolled_experts, expert.GROUPED_FROM
    expert._unrolled_experts, expert.GROUPED_FROM = rolled, ROUTED + 1
    try:
        return layer_row(call, ROUTED, "parent's rolled loop")[0]
    finally:
        expert._unrolled_experts, expert.GROUPED_FROM = unrolled, boundary


def held_rows(call):
    for held in (16, 32, 64, 128):
        for name, boundary in (("unrolled", ROUTED + 1), ("kernel", 1)):
            expert.GROUPED_FROM = boundary
            yield layer_row(call, held, name)[0]


def main(argv):
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("this sweep times the chip: no TPU here")
    what = set(argv) or {"tiles", "held", "parent"}
    served = kernel.tiles
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    emit(dict(device=dev.device_kind, D=D, FF=FF, routed=ROUTED, k=TOPK,
              boundary=expert.GROUPED_FROM, served_tiles={
                  c: list(served(B * T, TOPK, ROUTED, D, FF))
                  for c, (B, T, _) in CALLS.items()}))
    for call in CALLS:
        if "parent" in what:
            emit(parent_rolled(call))
        if "tiles" in what:
            for row in tiles_rows(call):
                emit(row)
            kernel.tiles = served
    if "held" in what:
        boundary = expert.GROUPED_FROM
        for row in held_rows("round_256"):
            emit(row)
        expert.GROUPED_FROM = boundary
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "sweep_grouped_experts.json").write_text(json.dumps(rows))


if __name__ == "__main__":
    main(sys.argv[1:])
