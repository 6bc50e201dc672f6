#!/usr/bin/env python
"""Time the Mamba mixer's prefill scan on the chip, an unroll a line
(chip only; ``PERF.md`` sec. 4 holds the table this wrote):

    python scripts/sweep_mamba_scan.py [unroll ...]

``nn/mamba.selective_scan`` at Jamba2-3B's widths (d_inner 5120, d_state
16, float32, batch 1), four layers in one program as a prefill runs them,
at two of the buckets the chat cell prefills (1,024 and 4,096 positions):
milliseconds a layer for each number of positions an iteration, beside
the floor of ``benchmark/lib/costs_jamba.prefill_scan_bytes_floor`` for a
kernel that keeps 256 positions' states on the core, at the chip's
bandwidth. Then the whole mixer (projections, convolution, scan) at the
largest bucket, as it is served (``mamba.SCAN_UNROLL``). (The parallel
form this replaced, an associative scan inside chunks of 64 to 1,024
positions, read 2.2 to 20.6 ms a layer at 1,024 and 9.7 to 87.9 at 4,096
where a position a step read 1.1 ms at 1,024: PR 40.)
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from benchmark.lib import common, costs_jamba
from pytorch_distributed_nn_tpu.nn import mamba
from pytorch_distributed_nn_tpu.runtime.device import configure_compile_cache

LAYERS, D, N, D_MODEL = 4, 5120, 16, 2560
BUCKETS = (1024, 4096)
HBM = 819e9


def timed(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def scan_ms(T, unroll):
    ks = jax.random.split(jax.random.key(T), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (1, T, D)))
    c = jax.random.normal(ks[1], (1, T, D))
    b = jax.random.normal(ks[2], (1, T, N))
    co = jax.random.normal(ks[3], (1, T, N))
    a = -jnp.exp(0.1 * jax.random.normal(ks[4], (N, D)))

    @jax.jit
    def layers(h, dt, c, b, co):
        y = 0.0
        for _ in range(LAYERS):
            yl, h = mamba.selective_scan(h, dt, c, b, co, a, unroll)
            y, c = y + yl, c + 1e-3 * yl    # a layer reads the last one's
        return y, h
    return 1e3 * timed(layers, jnp.zeros((1, N, D)), dt, c, b, co) / LAYERS


def mixer_ms(T):
    mixer = mamba.MambaMixer(d_inner=D, dtype=jnp.bfloat16,
                             param_dtype=jnp.bfloat16)
    u = jax.random.normal(jax.random.key(1), (1, T, D_MODEL), jnp.bfloat16)
    params = jax.jit(mixer.init)(jax.random.key(0), u[:, :8])

    @jax.jit
    def layers(params, u):
        for _ in range(LAYERS):
            u = u + mixer.apply(params, u)
        return u
    return 1e3 * timed(layers, params, u) / LAYERS


def main():
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("sweep_mamba_scan: no TPU; a time comes from "
                         "the chip")
    unrolls = [int(x) for x in sys.argv[1:]] or [1, 2, 4, 8, 16, 32]
    cfg = common.load_json(Path("benchmark/configs/jamba2_3b.json"))
    rows = []
    for T in BUCKETS:
        floor = costs_jamba.prefill_scan_bytes_floor(cfg, T, 256) \
            / costs_jamba.layer_counts(cfg)["mamba"] / HBM * 1e3
        for unroll in unrolls:
            row = dict(T=T, unroll=unroll,
                       scan_ms=round(scan_ms(T, unroll), 3),
                       floor_ms=round(floor, 4))
            print(json.dumps(row), flush=True)
            rows.append(row)
    row = dict(T=BUCKETS[-1], unroll=mamba.SCAN_UNROLL,
               mixer_ms=round(mixer_ms(BUCKETS[-1]), 3))
    print(json.dumps(row), flush=True)
    rows.append(row)
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "sweep_mamba_scan.json").write_text(json.dumps(
        dict(device=dev.device_kind, rows=rows), indent=1))


if __name__ == "__main__":
    main()
