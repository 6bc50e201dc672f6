"""Records the small trace with serve-loop spans that
``test_host_spans.py`` reads.

Run on the chip, once, when the trace format or the spans change:

    python benchmark/tests/record_spans.py   # writes chiprun_out/recorded_spans.xplane.pb

A two-layer decoder of width 64 behind the program's ``InferenceServer``,
three requests of a few tokens each, traced with the options of
``run.py``'s ``Tracer``. The server is started before the session and
parks between the requests, so the file holds every span of the serve
loop on one thread beside the device's planes. It prints the thread's
events and what ``host_spans`` makes of the file.
"""

import os
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    from benchmark.lib import host_spans, trace_reduce
    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.serve import (
        InferenceServer,
        ServingEngine,
    )

    out = Path.cwd() / "chiprun_out"
    out.mkdir(exist_ok=True)
    tdir = out / "record_spans"
    shutil.rmtree(tdir, ignore_errors=True)
    model = get_model(ModelConfig(
        name="llama3_8b", compute_dtype="bfloat16", dtype="bfloat16",
        extra=dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   mlp_dim=128, vocab_size=97)))
    params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    server = InferenceServer(
        ServingEngine(model, params, max_slots=4, max_seq_len=64,
                      block_size=16), idle_wait_s=0.002).start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, size=(n,)).astype(np.int32)
               for n in (5, 19, 19)]
    prompts[2] = prompts[1]     # the second block-aligned prefix again

    def wave():
        a = server.submit(prompts[0], 4)
        b = server.submit(prompts[1], 3)
        for r in (a, b):
            assert r.done.wait(600.0) and r.state == "done", r.state
        time.sleep(0.01)
        c = server.generate(prompts[2], 3, timeout=600.0)
        assert c.state == "done", c.state

    wave()   # compiles every program outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    time.sleep(0.01)
    wave()
    time.sleep(0.01)
    jax.profiler.stop_trace()
    server.stop(timeout=60.0)
    path = trace_reduce.find_xplane(str(tdir))
    shutil.copy(path, out / "recorded_spans.xplane.pb")
    print("bytes", os.path.getsize(path))
    for name, s, e, stats in host_spans.load_spans(path):
        print(f"  {name} {s:.0f} {e - s:.0f} {stats}")
    devs = trace_reduce.load(path)
    if not devs:
        print("no /device:TPU plane in the trace: this was not the chip")
        return 1
    for name, s, e in devs[min(devs)]["modules"]:
        print(f"  MODULE {name} {s:.0f} {e - s:.0f}")
    a = host_spans.analyze(path)
    print({k: v for k, v in a.items() if k != "spans"})
    print(jax.devices())
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)   # the serve loop's thread is a daemon; leave now
