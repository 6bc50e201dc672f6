#!/usr/bin/env python3
"""The control of a cell, on the chip, at the cell's own size.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 [--seconds s]

For each seed, in one process: build the cell as a run does, drive it
(training: the first three steps and no window; serving: a short window
at the cell's own load, long enough to finish the mix's longest
requests), read the program's numbers against the float32 reference,
then read the same numbers for the control: the reference computed in
the precision below the one the configuration states (float8 matmuls
for bfloat16 training, int8 weights for a bfloat16 served model).

One JSON line per seed; the last line holds the largest a sound run
gave and the smallest the control gave, per number. A limit is set
between the two (``benchmark/cells/<workload>.json``), and ``PERF.md``
lists the readings. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--no-control", action="store_true",
                    help="sound runs only (more seeds for the lower end)")
    args = ap.parse_args(argv)
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, config = bench_run.find_cell(bench, args.workload)
    traffic_file = BENCH / "traffic" / f"{cell['traffic']}.json"
    kind = common.load_json(traffic_file)["kind"]
    seconds = args.seconds if args.seconds is not None else \
        (0.0 if kind == "train" else 12.0)
    sound: dict = {}
    ctrl: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        run = bench_run.run_cell(
            workload=cell["name"], config_file=ROOT / config["file"],
            traffic_file=traffic_file,
            cell_file=BENCH / "cells" / f"{cell['name']}.json",
            chips=int(cell["chips"]), seed=seed, seconds=seconds,
            traced=False, control=not args.no_control)
        row = dict(seed=seed, seconds=round(time.perf_counter() - t, 1),
                   program={r["name"]: r["value"] for r in run["check"]})
        for name, v in row["program"].items():
            sound.setdefault(name, []).append(v)
        if "control" in run:
            row["control"] = {r["name"]: r["value"]
                              for r in run["control"]["numbers"]}
            row["control_correct"] = run["control"]["correct"]
            for name, v in row["control"].items():
                ctrl.setdefault(name, []).append(v)
        print(json.dumps(row), flush=True)
        del run
    print(json.dumps(dict(
        workload=cell["name"],
        sound_largest={k: max(v) for k, v in sound.items()},
        control_smallest={k: min(v) for k, v in ctrl.items()})), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
