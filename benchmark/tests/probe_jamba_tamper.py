"""Probe (chip only, by hand): which faults does the cell's check see?

    python3 benchmark/tests/probe_jamba_tamper.py [seed] [fault]

Runs ``jamba2_3b_chat_closed64`` for a short window with one fault in
how the program carries a Mamba layer's state (the reference regenerates
its own weights and carries nothing) and prints the numbers beside the
cell's limits. Under the harness's draw ``A`` is about -1 and the step
about 0.8, so a state forgets in a few positions: this is what says how
much of a fault the check still sees. ``fault``:

- ``state_zeroed`` (the default): every ``ssm_state`` leaf of a prefilled
  row zeroed as the row joins the batch (``_insert_row``): the first
  decode rounds of every request start from nothing;
- ``tail_one_off``: the convolution's carried inputs one position off as
  the row joins the batch (the newest of the three is lost, the others
  move up): the first three rounds of every request convolve the wrong
  inputs;
- ``padding_advances``: a prefill's bucket padding let through to the
  state and the tail (the model is told every fed position is real);
- ``none``: no fault, the cell as it is.

Also prints the shapes of the engine's cache leaves (the state beside
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
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

FAULT = sys.argv[2] if len(sys.argv) > 2 else "state_zeroed"
bench = common.load_json(ROOT / "BENCHMARK.json")
cell, config = bench_run.find_cell(bench, "jamba2_3b_chat_closed64")


def _on_leaf(tree, leaf: str, f):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: f(x) if getattr(path[-1], "key", "") == leaf else x,
        tree)


def tamper(engine):
    shapes: dict = {}
    for leaf in jax.tree.leaves(engine._cache):
        shapes[leaf.shape] = shapes.get(leaf.shape, 0) + 1
    print(f"engine cache: {shapes}, "
          f"{sum(x.nbytes for x in jax.tree.leaves(engine._cache))} bytes; "
          f"prefix cache {engine.prefix_cache}", flush=True)
    insert, mask_kw = engine_mod._insert_row, engine_mod._mask_kw
    if FAULT == "state_zeroed":
        engine_mod._insert_row = lambda batch, row, slot, **kw: insert(
            batch, _on_leaf(row, "ssm_state", jnp.zeros_like), slot, **kw)
    elif FAULT == "tail_one_off":
        engine_mod._insert_row = lambda batch, row, slot, **kw: insert(
            batch, _on_leaf(row, "conv_tail",
                            lambda x: jnp.roll(x, 1, axis=1)), slot, **kw)
    elif FAULT == "padding_advances":
        engine_mod._mask_kw = lambda model, mask: mask_kw(
            model, jnp.ones_like(mask) if mask.shape[1] > 1 else mask)


if FAULT not in ("state_zeroed", "tail_one_off", "padding_advances", "none"):
    raise SystemExit(f"unknown fault {FAULT!r}")
run = bench_run.run_cell(
    workload=cell["name"], config_file=ROOT / config["file"],
    traffic_file=ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json",
    cell_file=ROOT / "benchmark" / "cells" / f"{cell['name']}.json",
    chips=1, seed=int(sys.argv[1]) if len(sys.argv) > 1 else 2**31 + 5,
    seconds=12.0, traced=False, tamper=tamper)
print(f"fault {FAULT}:", run["correct"], run["check"], flush=True)
