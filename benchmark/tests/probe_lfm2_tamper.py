"""Probe (chip only, by hand): which faults does the cell's check see?

    python3 benchmark/tests/probe_lfm2_tamper.py [seed] [faults] [seconds]

Runs ``lfm2_8b_a1b_turns_closed64`` for a short window with one fault in
the program (the reference regenerates its own weights) and prints the
numbers beside the cell's limits, for each of ``faults`` (a list with
commas; the default all of ``benchmark/tests/tamper_lfm2.py``'s, in one
process, the seed one up for each): ``tail_one_off`` (the carried inputs
one position off at the hand-over), ``padding_let_through`` (a prefill's
padding let into the tail), ``experts_zeroed`` (every
``moe/experts_down`` zeroed), ``keys_zeroed`` (the three attention
layers' cached keys zeroed at the hand-over), or ``none``. A tail
reaches two positions: the first two are the mechanism this
configuration brought, and say how much of it the check sees.
``PERF.md`` sec. 7 has the readings.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import common  # noqa: E402
from benchmark.tests import tamper_lfm2  # noqa: E402
from pytorch_distributed_nn_tpu.serve import engine as engine_mod  # noqa: E402

SEED = int(sys.argv[1]) if len(sys.argv) > 1 else 2**31 + 5
FAULTS = sys.argv[2].split(",") if len(sys.argv) > 2 \
    else list(tamper_lfm2.FAULTS)
SECONDS = float(sys.argv[3]) if len(sys.argv) > 3 else 12.0
bench = common.load_json(ROOT / "BENCHMARK.json")
cell, config = bench_run.find_cell(bench, "lfm2_8b_a1b_turns_closed64")
sound = {n: getattr(engine_mod, n) for n in ("_insert_row", "_mask_kw")}

for k, fault in enumerate(FAULTS):
    # the last fault's programs are in jit's cache, and its patches in
    # the engine's module
    jax.clear_caches()
    for name, value in sound.items():
        setattr(engine_mod, name, value)

    def tamper(engine, fault=fault):
        if fault != "none":
            tamper_lfm2.apply(fault, engine, setattr)

    run = bench_run.run_cell(
        workload=cell["name"], config_file=ROOT / config["file"],
        traffic_file=ROOT / "benchmark" / "traffic"
        / f"{cell['traffic']}.json",
        cell_file=ROOT / "benchmark" / "cells" / f"{cell['name']}.json",
        chips=1, seed=SEED + k, seconds=SECONDS, traced=False,
        tamper=tamper)
    print(f"fault {fault} (seed {SEED + k}):", run["correct"], run["check"],
          flush=True)
    del run
