"""Probe (chip only, by hand): which faults does the cell's check see?

    python3 benchmark/tests/probe_brumby_tamper.py [seed] [fault]

Runs ``brumby_14b_reader_closed16`` for a short window with one fault in
how the program carries a retention layer's state (the reference builds
no state at all: it sums scores) and prints the numbers beside the
cell's limits. Under the harness's draw a gate is about 1/2, so a state
forgets in a few positions: this is what says how much of a fault the
check still sees. ``fault``:

- ``state_zeroed`` (the default): every ``ret_state`` leaf of a prefilled
  row zeroed as the row joins the batch (``_insert_row``): the first
  decode rounds of every request start from nothing;
- ``no_normaliser``: the normaliser left out (every ``ret_norm`` leaf
  zeroed at the same place, and again each round it would have grown
  from there: the quotient's denominator is what the rounds alone add);
- ``gate_at_one``: the gate held at 1 (the projection's kernel zeroed
  would make it 1/2; here ``log g`` is forced to 0): nothing is ever
  forgotten;
- ``padding_advances``: a prefill's bucket padding let through to the
  state (the model is told every fed position is real);
- ``none``: no fault, the cell as it is.

Also prints the shapes of the engine's cache leaves. ``PERF.md`` sec. 7
has the readings.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import common  # noqa: E402
from pytorch_distributed_nn_tpu.nn import retention  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

FAULTS = ("state_zeroed", "no_normaliser", "gate_at_one",
          "padding_advances", "none")
FAULT = sys.argv[2] if len(sys.argv) > 2 else "state_zeroed"
bench = common.load_json(ROOT / "BENCHMARK.json")
cell, config = bench_run.find_cell(bench, "brumby_14b_reader_closed16")


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
    if FAULT in ("state_zeroed", "no_normaliser"):
        leaf = {"state_zeroed": "ret_state",
                "no_normaliser": "ret_norm"}[FAULT]
        engine_mod._insert_row = lambda batch, row, slot, **kw: insert(
            batch, _on_leaf(row, leaf, jnp.zeros_like), slot, **kw)
    elif FAULT == "gate_at_one":
        whole = retention.power_retention
        retention.power_retention = \
            lambda S, z, q, k, v, log_g, real, **kw: whole(
                S, z, q, k, v, jnp.zeros_like(log_g), real, **kw)
    elif FAULT == "padding_advances":
        engine_mod._mask_kw = lambda model, mask: mask_kw(
            model, jnp.ones_like(mask) if mask.shape[1] > 1 else mask)


if FAULT not in FAULTS:
    raise SystemExit(f"unknown fault {FAULT!r} (of {FAULTS})")
run = bench_run.run_cell(
    workload=cell["name"], config_file=ROOT / config["file"],
    traffic_file=ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json",
    cell_file=ROOT / "benchmark" / "cells" / f"{cell['name']}.json",
    chips=1, seed=int(sys.argv[1]) if len(sys.argv) > 1 else 2**31 + 5,
    seconds=12.0, traced=False, tamper=tamper)
print(f"fault {FAULT}:", run["correct"], run["check"], flush=True)
