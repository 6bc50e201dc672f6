"""Probe (chip only, by hand): which faults does the cell's check see?

    python3 benchmark/tests/probe_sdar_tamper.py [seed] [fault] [seconds]

Runs ``sdar_30b_a3b_gen_closed64`` for a short window with one fault in
the program (the reference regenerates its own weights) and prints the
numbers beside the cell's limits. ``fault`` is one of
``benchmark/tests/tamper_sdar.py``'s: ``causal_block`` (the default:
the mask inside a block made causal), ``commit_skipped`` (the commit
forward's keys and values left out: a committed block keeps its last
denoising step's rows), ``experts_zeroed`` (every ``moe/experts_down``
zeroed), or ``none``. The first two are the mechanism this
configuration brought. ``PERF.md`` sec. 7 has the readings.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import common  # noqa: E402
from benchmark.tests import tamper_sdar  # noqa: E402

SEED = int(sys.argv[1]) if len(sys.argv) > 1 else 2**31 + 5
FAULT = sys.argv[2] if len(sys.argv) > 2 else "causal_block"
SECONDS = float(sys.argv[3]) if len(sys.argv) > 3 else 12.0
bench = common.load_json(ROOT / "BENCHMARK.json")
cell, config = bench_run.find_cell(bench, "sdar_30b_a3b_gen_closed64")


def tamper(engine):
    if FAULT != "none":
        tamper_sdar.apply(FAULT, engine, setattr)


run = bench_run.run_cell(
    workload=cell["name"], config_file=ROOT / config["file"],
    traffic_file=ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json",
    cell_file=ROOT / "benchmark" / "cells" / f"{cell['name']}.json",
    chips=1, seed=SEED, seconds=SECONDS, traced=False, tamper=tamper)
print(f"fault {FAULT}:", run["correct"], run["check"], flush=True)
