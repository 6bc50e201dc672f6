#!/usr/bin/env python3
"""One rung of the knee sweep (see README.md): the open-loop cell at
another rate, with what tells whether the server sustains it.

    python3 benchmark/sweep.py --workload mistral7b_chat_replay --rates 3,4,5,6,7 --seconds 30

One process, one engine, one window per rate (token ids from another
seed each, so that no rung finds the last one's prompts in the prefix
cache). Prints one JSON line per rate: requests due, refused and unfinished; the backlog
(due and not yet admitted) at a third, two thirds and the end of the
window; both tails; occupancy. Skips the output check. The rate that
the cell then runs at is written into its traffic file by hand.
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
from benchmark.lib import common, readers, serving  # noqa: E402
from benchmark.lib import traffic as traffic_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, config = bench_run.find_cell(bench, args.workload)
    cfg = common.load_json(ROOT / config["file"])
    traf = common.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    rates = [float(r) for r in args.rates.split(",")]
    ref = common.load_module(
        (ROOT / config["file"]).parent / f"{cfg['program']['reference']}.py",
        "benchmark_ref_sweep")
    common.place_compile_cache()

    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("benchmark: no TPU")
    phases = common.Phases(time.perf_counter())
    traf["arrivals"]["rate_rps"] = max(rates)
    model, engine, server = serving.build(cfg, ref, traf, args.seed, phases)
    serving.warm_up(server, traffic_lib.schedule(traf, args.seconds),
                    cfg["vocab_size"], args.seed)
    for k, rate in enumerate(rates):
        traf["arrivals"]["rate_rps"] = rate
        sched = traffic_lib.schedule(traf, args.seconds)
        run = serving.run_window(server, engine, traf, sched,
                                 cfg["vocab_size"], args.seed + 1 + k,
                                 args.seconds, phases)
        sent, t0 = run["sent"], run["t0"]

        def backlog(frac: float) -> int:
            at = t0 + frac * args.seconds
            return sum(1 for s in sent if s.due <= at and (
                s.request is None or not s.request.t_admit
                or s.request.t_admit > at))

        refused = sum(1 for s in sent if s.request is not None
                      and s.request.state == "rejected")
        print(json.dumps(dict(
            rate_rps=rate, due=len(sent), refused=refused,
            unfinished=sum(1 for s in sent if not s.ok),
            backlog=[backlog(1 / 3), backlog(2 / 3), backlog(1.0)],
            ttft_p50_ms=readers.pct(readers.ttfts_ms(run), 50),
            ttft_p90_ms=readers.pct(readers.ttfts_ms(run), 90),
            itl_p50_ms=readers.pct(readers.itls_ms(run), 50),
            itl_p95_ms=readers.pct(readers.itls_ms(run), 95),
            occupancy_pct=readers.batch_occupancy_pct(run),
            decode_round_p50_ms=readers.decode_round_p50_ms(run),
            tokens_per_s=readers.tokens_in_window(run)
            / readers.window_s(run),
            drain_s=max((s.closed for s in sent if s.closed),
                        default=run["t1"]) - run["t1"])), flush=True)
    server.stop(timeout=120.0)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
