"""Records the small device trace that ``test_trace_reduce.py`` reads.

Run on the chip, once, when the trace format changes:

    python benchmark/tests/record_trace.py   # writes chiprun_out/recorded.xplane.pb

Two named programs with a host sleep between them, so that the recorded
trace has a known shape: busy spans of ``jit_prog_a`` and ``jit_prog_b``,
and one long idle gap after a ``jit_prog_b`` and before a ``jit_prog_a``.
It prints the planes, lines and first events, which is how the reduction
in ``benchmark/lib/trace_reduce.py`` was written against a real file.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    out = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tdir = os.path.join(out, "record_trace")
    shutil.rmtree(tdir, ignore_errors=True)

    @jax.jit
    def prog_a(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.01
        return x

    @jax.jit
    def prog_b(x):
        return x * 2.0 + 1.0

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready((prog_a(x), prog_b(x)))
    jax.profiler.start_trace(tdir)
    y = prog_a(x)
    y = prog_a(y)
    y = prog_b(y)
    jax.block_until_ready(y)
    time.sleep(0.02)
    y = prog_a(y)
    y = prog_b(y)
    jax.block_until_ready(y)
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))
    shutil.copy(paths[0], os.path.join(out, "recorded.xplane.pb"))
    print("bytes", os.path.getsize(paths[0]))
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(paths[0])
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:6]) if hasattr(e, "stats") else "")
    print(jax.devices())
    return 0


if __name__ == "__main__":
    sys.exit(main())
