"""Probe (chip only, by hand): Brumby's two retention kernels at the
cell's shapes, each against its ``jax.numpy`` oracle.

    python3 benchmark/tests/probe_brumby_kernels.py [chunk ...]

Prints one JSON line a reading: the decode round's ``retention_step``
over 16 rows of 8 heads (a state of 8,704 x 128 a head) against the
XLA form's three passes, in milliseconds and as a share of the chip's
819 GB/s for the state in and out; and a prefill's whole retention
(4,096 positions of one row) at each chunk length given (default 64,
128, 256), kernel and XLA form, with the kernel's own time apart.
``nn/retention.CHUNK`` was read here (``PERF.md`` sec. 6, PR 50).
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pytorch_distributed_nn_tpu.nn import retention  # noqa: E402
from pytorch_distributed_nn_tpu.ops.pallas import retention as kernel  # noqa: E402

B, KV, G, HD = 16, 8, 5, 128
D = kernel.state_rows(HD)
f32, bf16 = jnp.float32, jnp.bfloat16


def timed(fn, carry, *args, reps=10):
    """Milliseconds a call; ``fn(carry, *args) -> (out, carry)``, the
    carry donated."""
    out, carry = fn(carry, *args)
    jax.block_until_ready(carry)
    t = time.perf_counter()
    for _ in range(reps):
        out, carry = fn(carry, *args)
    jax.block_until_ready((out, carry))
    return (time.perf_counter() - t) / reps * 1e3, out, carry


def say(**kw):
    print(json.dumps(kw), flush=True)


ks = jax.random.split(jax.random.key(50), 12)
print(jax.devices()[0].device_kind, flush=True)

# -- the round ---------------------------------------------------------------
S = jax.random.normal(ks[0], (B, KV, D, HD), f32)
g = jax.nn.sigmoid(jax.random.normal(ks[1], (B, KV)))
k, v = (jax.random.normal(ks[2], (2, B, KV, HD), bf16).astype(f32))
q = jax.random.normal(ks[3], (B, KV, G, HD), bf16).astype(f32)
act = jnp.ones((B,), bool)
xla = jax.jit(lambda S, g, k, v, q: retention.step_xla(S, g, k, v, q),
              donate_argnums=(0,))
want_y, want_S = xla(S + 0, g, k, v, q)
got_y, got_S = kernel.step(S + 0, g, k, v, q, act)
say(what="step kernel against step_xla",
    y_err=float(jnp.abs(got_y - want_y).max() / jnp.abs(want_y).max()),
    S_err=float(jnp.abs(got_S - want_S).max()))
moved = 2 * B * KV * D * HD * 4
for name, fn in (("kernel", lambda S, *a: kernel.step(S, *a, act)),
                 ("xla", xla)):
    ms, _, _ = timed(fn, S + 0, g, k, v, q)
    say(what=f"step {name}, {B} rows", ms=ms,
        hbm_share=moved / (ms * 1e-3) / 819e9)
half = jnp.arange(B) % 2 == 0
ms, _, _ = timed(lambda S, *a: kernel.step(S, *a, half), S + 0, g, k, v, q)
say(what="step kernel, every second row idle", ms=ms)
del S, want_S, got_S

# -- the prefill -------------------------------------------------------------
T = 4096
S0 = jnp.zeros((1, KV, D, HD), f32)
z0 = jnp.zeros((1, KV, D), f32)
q = jax.random.normal(ks[4], (1, T, KV, G, HD), bf16)
k, v = jax.random.normal(ks[5], (2, 1, T, KV, HD), bf16)
log_g = jax.nn.log_sigmoid(jax.random.normal(ks[6], (1, T, KV)) + 2.0)
real = jnp.ones((1, T), bool)
for C in [int(a) for a in sys.argv[1:]] or [64, 128, 256]:
    n = T // C
    outs = {}
    for name, on_core in (("kernel", True), ("xla", False)):
        fn = jax.jit(lambda S, z, C=C, on_core=on_core:
                     (lambda y, S, z: (y, (S, z)))(*retention.power_retention(
                         S, z, q, k, v, log_g, real, chunk=C,
                         on_core=on_core)), donate_argnums=(0, 1))
        ms, y, (S1, _) = timed(lambda c, fn=fn: fn(*c), (S0 + 0, z0 + 0),
                               reps=3)
        outs[name] = (y, S1)
        say(what=f"mixer over {T} positions, chunks of {C}, {name}", ms=ms)
    say(what=f"chunks of {C}: kernel against xla",
        y_err=float(jnp.abs(outs["kernel"][0] - outs["xla"][0]).max()),
        S_err=float(jnp.abs(outs["kernel"][1] - outs["xla"][1]).max()
                    / jnp.abs(outs["xla"][1]).max()))
    qc = jax.random.normal(ks[7], (1, KV, n, G, C, HD), bf16)
    kc, vc = jax.random.normal(ks[8], (2, 1, KV, n, C, HD), bf16)
    gc = jnp.full((1, KV, n), 0.5, f32)
    ms, _, _ = timed(lambda S: kernel.chunk(S, qc, kc, vc, gc), S0 + 0,
                     reps=3)
    need = 2.0 * (G + 1) * KV * 8256 * HD * T
    say(what=f"retention_chunk alone, chunks of {C}", ms=ms,
        flops_share=need / (ms * 1e-3) / 197e12)
