#!/usr/bin/env python
"""Time the Mamba mixer's prefill scan on the chip, an execution a line
(chip only; ``PERF.md`` sec. 4 and 6 hold the tables this wrote):

    python scripts/sweep_mamba_scan.py [unroll ...]

``nn/mamba.selective_scan`` at Jamba2-3B's widths (d_inner 5120, d_state
16, float32 state, batch 1), four layers in one program as a prefill
runs them, at two of the buckets the chat cell prefills (1,024 and 4,096
positions), milliseconds a layer: the Pallas kernel
(``ops/pallas/selective_scan``) at the chunks and lane tiles tried, ``c``
in bf16 as it is served, each against ``lax.scan`` on the same operands
(the largest difference of ``y`` and of the state, and whether a
``dt = 0`` tail held the state bit for bit); then ``lax.scan`` for each
number of positions an iteration. Beside them the two floors: the bytes
of ``benchmark/lib/costs_jamba.prefill_scan_bytes_floor`` (a kernel that
keeps a chunk's states on the core) at the chip's bandwidth, and the
recurrence's operations (an ``exp``, four products, an add and the sum's
add for each of ``d_state x d_inner`` elements a position) at the vector
unit's rate (four ALU slots a bundle over ``8 x 128`` lanes at the core's
clock). Then the whole mixer (projections, convolution, scan) at the
largest bucket in both executions: under ``decode=True`` as a prefill
runs it (the kernel) and uncached (``lax.scan``, ``mamba.SCAN_UNROLL``).
The four layers of a program read the same operands, which a model's
do not: XLA then shares one broadcast of ``b`` and ``c_out`` among them
and, given the kernel's cost estimate, copies ``dt`` aside for layers 2
to 4 (84 MB at 4,096; the same kernel without an estimate read 0.1 ms
less there, PERF.md sec. 6): read the kernel's rows against each other
and against the mixer's line, which holds what a layer pays.
(The parallel form these replaced, an associative scan inside chunks of
64 to 1,024 positions, read 2.2 to 20.6 ms a layer at 1,024 and 9.7 to
87.9 at 4,096 where a position a step read 1.1 ms at 1,024: PR 40.)
"""

import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from benchmark.lib import common, costs_jamba
from pytorch_distributed_nn_tpu.nn import mamba
from pytorch_distributed_nn_tpu.ops.pallas import selective_scan as kernel
from pytorch_distributed_nn_tpu.runtime.device import configure_compile_cache

LAYERS, D, N, D_MODEL = 4, 5120, 16, 2560
BUCKETS = (1024, 4096)
HBM = 819e9
# v5e's core: 4 MXUs of 128 x 128 at 197 TFLOP/s is 1.5 GHz; the
# compiler's bundles hold four vector-ALU slots of (8, 128) lanes
VECTOR_OPS = 4 * 8 * 128 * 1.5e9
SCAN_OPS = 7                   # an element a position: see the docstring
TILES = ((128, 1024), (256, 1024), (512, 1024), (256, 512))


def timed(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def operands(T, c_dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(T), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (1, T, D)))
    c = jax.random.normal(ks[1], (1, T, D)).astype(c_dtype)
    b = jax.random.normal(ks[2], (1, T, N))
    co = jax.random.normal(ks[3], (1, T, N))
    a = -jnp.exp(0.1 * jax.random.normal(ks[4], (N, D)))
    return jnp.zeros((1, N, D)), dt, c, b, co, a


def layers_ms(scan, T, c_dtype=jnp.float32):
    """``scan(h, dt, c, b, co, a) -> (y, h)`` four layers deep: a layer
    starts from the last one's state, and what is kept of its ``y`` is
    one position, so that the program around the scans moves nothing of
    their size."""
    *xs, a = operands(T, c_dtype)

    @jax.jit
    def layers(h, dt, c, b, co):
        last = 0.0
        for _ in range(LAYERS):
            y, h = scan(h, dt, c, b, co, a)
            last = last + y[:, -1]
        return last, h
    return 1e3 * timed(layers, *xs) / LAYERS


def scan_ms(T, unroll):
    return layers_ms(functools.partial(mamba.selective_scan,
                                       unroll=unroll), T)


def kernel_row(T, chunk, lanes):
    """The kernel's time, and what it computes against ``lax.scan``'s
    on the same operands, the second half of the positions at step 0."""
    run = functools.partial(kernel.scan, chunk=chunk, lanes=lanes)
    h, dt, c, b, co, a = operands(T, jnp.bfloat16)
    h = h + 1.0
    dt = dt.at[:, T // 2:].set(0.0)
    y, h1 = run(h, dt, c, b, co, a)
    want_y, want_h = jax.jit(mamba.selective_scan)(h, dt, c, b, co, a)
    _, held = run(h, *(x[:, :T // 2] for x in (dt, c, b, co)), a)
    return dict(
        T=T, chunk=chunk, lanes=lanes,
        scan_ms=round(layers_ms(run, T, jnp.bfloat16), 3),
        y_diff=float(jnp.abs(y - want_y).max()),
        y_size=float(jnp.abs(want_y).max()),
        h_diff=float(jnp.abs(h1 - want_h).max()),
        tail_held=bool((held == h1).all()))


def mixer_ms(T, cached):
    mixer = mamba.MambaMixer(d_inner=D, dtype=jnp.bfloat16,
                             param_dtype=jnp.bfloat16)
    u = jax.random.normal(jax.random.key(1), (1, T, D_MODEL), jnp.bfloat16)
    variables = jax.jit(functools.partial(mixer.init, decode=cached))(
        jax.random.key(0), u[:, :8])

    @jax.jit
    def layers(variables, u):
        for _ in range(LAYERS):
            if cached:
                out, _ = mixer.apply(variables, u, decode=True,
                                     mutable=["cache"])
            else:
                out = mixer.apply(variables, u)
            u = u + out
        return u
    return 1e3 * timed(layers, variables, u) / LAYERS


def main():
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("sweep_mamba_scan: no TPU; a time comes from "
                         "the chip")
    unrolls = [int(x) for x in sys.argv[1:]] or [1, 2, 4, 8, 16, 32]
    cfg = common.load_json(Path("benchmark/configs/jamba2_3b.json"))
    rows = []

    def say(**row):
        print(json.dumps(row), flush=True)
        rows.append(row)

    for T in BUCKETS:
        say(T=T,
            bytes_floor_ms=round(
                costs_jamba.prefill_scan_bytes_floor(cfg, T, kernel.CHUNK)
                / costs_jamba.layer_counts(cfg)["mamba"] / HBM * 1e3, 4),
            ops_floor_ms=round(SCAN_OPS * T * N * D / VECTOR_OPS * 1e3, 4))
        for chunk, lanes in TILES:
            say(**kernel_row(T, chunk, lanes))
        for unroll in unrolls:
            say(T=T, unroll=unroll, scan_ms=round(scan_ms(T, unroll), 3))
    say(T=BUCKETS[-1], tiles=kernel.tiles(BUCKETS[-1], D),
        mixer_ms=round(mixer_ms(BUCKETS[-1], True), 3))
    say(T=BUCKETS[-1], unroll=mamba.SCAN_UNROLL,
        mixer_ms=round(mixer_ms(BUCKETS[-1], False), 3))
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "sweep_mamba_scan.json").write_text(json.dumps(
        dict(device=dev.device_kind, rows=rows), indent=1))


if __name__ == "__main__":
    main()
