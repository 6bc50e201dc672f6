"""One run of a cell with every per-layer metric read that needs no
trace, by hand, on the chip:

    python benchmark/tests/probe_loop_records.py --workload <cell> --seed <n> \
        [--seconds 51] [--trace 0] [--watchdog 1]

``run.py`` reads a cell's per-layer metrics in its traced run only
(``metrics_for``), and the serve loop's own account (PR 37:
``lib/loop_records.py``) exists in every run. This is ``run.py`` with
that one difference: after the same ``run_cell`` it reads the cell's
end-to-end metrics and every per-layer metric listed for it (a reader
that finds nothing says nothing), and its last line adds the window's
seconds by phase and its three longest rounds with what filled them.
The program's own log lines (the serve loop's line at ``stop()``) go to
standard error.

``--watchdog 1`` starts a thread that wakes every 5 ms and keeps the
longest gaps between two of its wake-ups, with the CPU seconds the
whole process spent over each. Set beside a stalled round's record it
says whose the stall was: the watchdog woke on time, so the loop's
thread alone stood still (a lock, a call into the runtime); it did not
and the process burnt a core meanwhile, so another thread held the
interpreter; it did not and the process spent nothing, so the machine
stood still under every thread.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import threading
import time
from pathlib import Path

_T_IMPORT = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import common, loop_records  # noqa: E402


class Watchdog(threading.Thread):
    """Wakes every ``tick`` seconds and keeps every wake-up that came
    over 50 ms late (the last 1,000): seconds late, when it woke on
    ``time.monotonic()``, and the CPU seconds the process spent since a
    baseline at most a tenth of a second older than the wait."""

    def __init__(self, tick: float = 0.005) -> None:
        super().__init__(name="probe-watchdog", daemon=True)
        self.tick = tick
        self.gaps: list = []

    def run(self) -> None:
        last, cpu, cpu_at = time.monotonic(), time.process_time(), 0.0
        while True:
            time.sleep(self.tick)
            now = time.monotonic()
            late = now - last - self.tick
            if late > 0.05:
                self.gaps.append((late, now, time.process_time() - cpu))
                del self.gaps[:-1000]
            if late > 0.05 or now - cpu_at > 0.1:
                cpu, cpu_at = time.process_time(), now
            last = now


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--watchdog", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    dog = Watchdog() if args.watchdog else None
    if dog is not None:
        dog.start()
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, config = bench_run.find_cell(bench, args.workload)
    run = bench_run.run_cell(
        workload=cell["name"], config_file=ROOT / config["file"],
        traffic_file=bench_run.BENCH / "traffic" / f"{cell['traffic']}.json",
        cell_file=bench_run.BENCH / "cells" / f"{cell['name']}.json",
        chips=int(cell["chips"]), seed=args.seed, seconds=args.seconds,
        traced=bool(args.trace), t_start=_T_IMPORT)
    line = bench_run.result_line(
        run, bench_run.metrics_for(bench, cell["name"], False),
        bool(args.trace))
    for entry in bench_run.metrics_for(bench, cell["name"], True):
        try:
            line["metrics"].update(bench_run.read_metrics([entry], run))
        except (KeyError, TypeError, AttributeError) as e:
            # a reader written for the traced run only
            common.log(f"{entry['name']}: not readable here ({e!r})")
    a = loop_records.account(run)
    if a is not None:
        from pytorch_distributed_nn_tpu.obs import goodput

        line["loop"] = dict(
            window_s=a["window_s"], covered_s=a["covered_s"],
            rounds=a["rounds"], by_phase=a["by_phase"], gc_s=a["gc_s"],
            longest=[goodput.describe_round(r, run["t0"])
                     for r in a["longest"]])
    if dog is not None:
        # the five longest waits that ended inside the window
        line["watchdog"] = [
            dict(late_s=late, at_s=at - run["t0"], process_cpu_s=spent)
            for late, at, spent in sorted(
                g for g in dog.gaps if run["t0"] <= g[1] < run["t1"]
            )[::-1][:5]]
    line["seed"] = args.seed
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
