"""Probe (chip only, by hand): which faults does the cell's check see?

    python3 benchmark/tests/probe_kexaone_tamper.py [seed] [fault]

Runs ``k_exaone_reason_closed32`` for a short window with one fault in
the program (the reference regenerates its own weights) and prints the
numbers beside the cell's limits. ``fault``:

- ``held_experts`` (the default): every ``moe/experts_down`` zeroed, the
  tamper of ``test_harness_kexaone.py``. A held expert carries ~1/8 of a
  token's routed weight x 2.5 here (~0.3 a pick, about one pick a token
  a layer), against ~0.04 in LongCat's cell;
- ``ring_one_off``: a decode round writes position p to ring row
  (p + 1) mod 128, the other tamper of that file.

Also prints the shapes of the engine's cache leaves (the rings beside
the rows by position). ``PERF.md`` sec. 7 has the readings.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import common  # noqa: E402
from pytorch_distributed_nn_tpu.nn import attention  # noqa: E402

FAULT = sys.argv[2] if len(sys.argv) > 2 else "held_experts"
bench = common.load_json(ROOT / "BENCHMARK.json")
cell, config = bench_run.find_cell(bench, "k_exaone_reason_closed32")
WINDOW = common.load_json(ROOT / config["file"])["sliding_window"]


def tamper(engine):
    # the engine's own cache tree: which leaves are rings, and its size
    shapes: dict = {}
    for leaf in jax.tree.leaves(engine._cache):
        shapes[leaf.shape] = shapes.get(leaf.shape, 0) + 1
    print(f"engine cache: {shapes}, "
          f"{sum(x.nbytes for x in jax.tree.leaves(engine._cache))} bytes; "
          f"prefix cache {engine.prefix_cache}", flush=True)
    if FAULT == "ring_one_off":
        write = attention._row_update
        attention._row_update = lambda buf, new, starts: write(
            buf, new, (starts + 1) % WINDOW
            if buf.shape[1] == WINDOW else starts)
        return

    def fault(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        return jnp.zeros_like(leaf) \
            if name.endswith("moe/experts_down") else leaf
    engine.params = jax.tree_util.tree_map_with_path(fault, engine.params)


if FAULT not in ("held_experts", "ring_one_off"):
    raise SystemExit(f"unknown fault {FAULT!r}")
run = bench_run.run_cell(
    workload=cell["name"], config_file=ROOT / config["file"],
    traffic_file=ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json",
    cell_file=ROOT / "benchmark" / "cells" / f"{cell['name']}.json",
    chips=1, seed=int(sys.argv[1]) if len(sys.argv) > 1 else 2**31 + 5,
    seconds=12.0, traced=False, tamper=tamper)
print(f"fault {FAULT}:", run["correct"], run["check"], flush=True)
