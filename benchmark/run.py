#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name from ``BENCHMARK.json``:
its configuration (``benchmark/configs/<config>.json`` with the plain
reference beside it), its traffic (``benchmark/traffic/<traffic>.json``),
its limits (``benchmark/cells/<workload>.json``) and one reader per
metric (``benchmark/metrics/<metric>.py``). ``README.md`` says how a
later PR adds any of them without touching a file that is there.

Fails, with no result line, when JAX finds no TPU or another number of
chips than the cell asks for. The last line of standard output is the
result; earlier lines say what set-up spent where and every number the
check compared, beside its limit.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path

_T_IMPORT = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from benchmark.lib import common  # noqa: E402
from benchmark.lib.common import log  # noqa: E402


class Tracer:
    """A few seconds of profiler trace inside the window, taken by a
    thread of its own; reduced after the window closed."""

    def __init__(self, out_dir: Path, offset_share: float = 0.4,
                 max_seconds: float = 4.0) -> None:
        self.dir = out_dir
        self.offset_share = offset_share
        self.max_seconds = max_seconds
        self.tt0 = self.tt1 = 0.0
        self._thread = None
        self.error = None

    def _body(self, t0: float, seconds: float) -> None:
        import jax

        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            length = min(self.max_seconds, 0.3 * seconds)
            time.sleep(max(0.0, t0 + self.offset_share * seconds
                           - time.monotonic()))
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.tt0 = time.monotonic()
            time.sleep(length)
            self.tt1 = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported by join()
            self.error = e

    def start_in_background(self, t0: float, seconds: float) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = threading.Thread(target=self._body,
                                        args=(t0, seconds),
                                        name="tracer", daemon=True)
        self._thread.start()

    def join(self) -> None:
        self._thread.join(300.0)
        if self.error is not None:
            raise self.error

    def summary(self) -> dict:
        from benchmark.lib import trace_reduce

        self.join()
        devs = trace_reduce.load(trace_reduce.find_xplane(str(self.dir)))
        span = [(s, e) for d in devs.values() for k in ("modules", "ops")
                for _, s, e in d[k]]
        if not span:
            raise SystemExit("benchmark: the trace holds no device "
                             "operation")
        window = (max(e for _, e in span) - min(s for s, _ in span)) / 1e9
        out = trace_reduce.summarize(devs, window)
        out["host_window"] = (self.tt0, self.tt1)
        return out


def find_cell(bench: dict, workload: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_for(bench: dict, workload: str, traced: bool) -> list:
    """The metrics this cell reports in this kind of run: its end-to-end
    metrics untraced, its per-layer metrics traced. An entry with a
    ``workloads`` key belongs to the cells it lists; a per-layer entry
    without one belongs to every cell that reports what it ``moves``."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def read_metrics(entries: list, run: dict) -> dict:
    out = {}
    for m in entries:
        path = BENCH / "metrics" / f"{m['name']}.py"
        if not path.exists():
            raise SystemExit(f"benchmark: no reader {path}")
        mod = common.load_module(
            path, "benchmark_metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:   # a reader that finds nothing says nothing
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(*, workload: str, config_file: Path, traffic_file: Path,
             cell_file: Path, chips: int, seed: int, seconds: float,
             traced: bool, check_device: bool = True, control: bool = False,
             tamper=None, t_start: float | None = None) -> dict:
    """One run of one cell: set-up, window, check. Returns the run's
    records (what the metric readers read). ``control`` also reads the
    lower-precision control's numbers; ``tamper`` (tests only) is called
    with the system under test once it is built. ``t_start`` is this
    module's first line on ``time.perf_counter()``'s clock (``/proc``'s
    process start time was tried and read 833 s off in one run of seven
    on the chip's machine, PR 25)."""
    t_start = time.perf_counter() if t_start is None else t_start
    phases = common.Phases(t_start)
    cfg = common.load_json(config_file)
    traf = common.load_json(traffic_file)
    limits = common.load_json(cell_file)
    ref = common.load_module(
        config_file.parent / f"{cfg['program']['reference']}.py",
        "benchmark_ref_" + Path(cfg["program"]["reference"]).name)
    cache_dir = common.place_compile_cache()

    import jax

    phases.close("imports")
    devices = jax.devices()
    phases.close("claim_chip")
    if check_device:
        if devices[0].platform != "tpu":
            raise SystemExit(f"benchmark: no TPU (jax.devices()[0] is "
                             f"{devices[0].platform}); this benchmark "
                             f"does not fall back to the CPU")
        common.peaks(devices[0].device_kind)
    if len(devices) != chips:
        raise SystemExit(f"benchmark: cell {workload} asks for {chips} "
                         f"chip(s), JAX reports {len(devices)}")
    meter = common.CompileMeter()
    log(f"cell {workload}: seed {seed}, {seconds} s, trace {int(traced)}, "
        f"device {devices[0].device_kind} x{len(devices)}, compile cache "
        f"{cache_dir}")
    tracer = Tracer(ROOT / ".bench_trace" / workload) if traced else None
    kind = traf["kind"]
    if kind == "train":
        from benchmark.lib import training

        trainer, batch = training.build(cfg, ref, traf, seed, chips, phases)
        if tamper is not None:
            tamper(trainer)
        first = training.first_steps(trainer, cfg, ref, seed, phases)
        batches = [trainer.dataset.batch(i) for i in range(3)]
        mark = meter.mark()
        if seconds > 0:
            run = training.run_window(trainer, traf, batch, seconds, tracer)
        else:   # the control's readings need no window
            run = dict(kind="train", t0=0.0, t1=0.0, steps=0, batch=batch,
                       setup_done=time.perf_counter())
        run["compile"] = meter.since(mark)
        run["device"] = common.device_facts(devices)
        trainer.close()
        del trainer
        gc.collect()
        from benchmark.lib import check

        t = time.perf_counter()
        want = ref.first_steps(cfg, seed, batches, shards=chips,
                               total_steps=int(traf["schedule_steps"]))
        numbers = check.training_numbers(first, want)
        log(f"reference: 3 steps of batch {batch} in float32, "
            f"{time.perf_counter() - t:.1f} s; losses {want['losses']} "
            f"against the program's {first['losses']}")
        ctrl = None
        if control:
            low = ref.first_steps(cfg, seed, batches, shards=chips,
                                  total_steps=int(traf["schedule_steps"]),
                                  precision="fp8")
            ctrl = check.training_numbers(low, want)
    else:
        from benchmark.lib import check, serving
        from benchmark.lib import traffic as traffic_lib

        sched = traffic_lib.schedule(traf, seconds)
        model, engine, server = serving.build(cfg, ref, traf, seed, phases)
        if tamper is not None:
            tamper(engine)
        serving.warm_up(server, serving.flat_schedule(sched, kind),
                        cfg["vocab_size"], seed)
        phases.close("warm_up")
        mark = meter.mark()
        run = serving.run_window(server, engine, traf, sched,
                                 cfg["vocab_size"], seed, seconds, phases,
                                 tracer)
        run["compile"] = meter.since(mark)
        run["device"] = common.device_facts(devices)
        _log_window(run)
        # drop the program's state so that the reference has the chip
        server.stop(timeout=120.0)
        del model, engine, server
        gc.collect()
        numbers, ctrl = check.serving_numbers(
            cfg, ref, seed, run, int(limits["sample"]), control)
    ok, rows = check.judge(numbers, limits["limits"])
    if ctrl is not None:
        log("control (the reference in the precision below):")
        c_ok, c_rows = check.judge(ctrl, limits["limits"])
        run["control"] = dict(correct=c_ok, numbers=c_rows)
    peak_after = common.device_facts(devices)["memory_peak_bytes"]
    if peak_after > run["device"]["memory_peak_bytes"]:
        log(f"note: the reference raised the process's peak to "
            f"{peak_after} bytes; the program's "
            f"{run['device']['memory_peak_bytes']} is reported")
    run.update(workload=workload, cfg=cfg, traffic=traf, chips=chips,
               seed=seed,
               # the TPU runtime takes 5 to 20 s to hand over the chip, on
               # the same code and machine (PERF.md sec. 5): not the
               # repo's time, and the one part of set-up that is not steady
               setup_s=run["setup_done"] - t_start
               - phases.seconds["claim_chip"],
               setup_phases=phases.seconds, correct=ok, check=rows,
               peaks=common.peaks(devices[0].device_kind)
               if check_device else None,
               trace=tracer.summary() if tracer is not None else None)
    return run


def _log_window(run: dict) -> None:
    """What a reader of a run that came out slow looks at first: did
    anything compile in the window, and when were the longest waits."""
    t0 = run["t0"]
    gaps = sorted(((b - a, a - t0) for s in run["sent"]
                   for a, b in zip(s.arrivals, s.arrivals[1:])),
                  reverse=True)[:3]
    firsts = sorted(((s.arrivals[0] - s.due, s.due - t0)
                     for s in run["sent"] if s.arrivals), reverse=True)[:3]
    rounds = run["round_seconds"]
    from benchmark.lib import readers
    tt, it = readers.ttfts_ms(run), readers.itls_ms(run)
    if tt and it:
        log("tails (ms): ttft mean %.2f p50 %.2f p75 %.2f p90 %.2f p95 %.2f "
            "p99 %.2f; itl mean %.2f p50 %.2f p90 %.2f p95 %.2f p99 %.2f" % (
                sum(tt) / len(tt), *(readers.pct(tt, q)
                                     for q in (50, 75, 90, 95, 99)),
                sum(it) / len(it), *(readers.pct(it, q)
                                     for q in (50, 90, 95, 99))))
    log(f"window: {len(run['sent'])} requests, {len(rounds)} decode rounds "
        f"(longest {max(rounds, default=0) * 1e3:.1f} ms), compile events "
        f"{run['compile']}; longest token gaps (ms at s) "
        f"{[(round(g * 1e3, 1), round(at, 2)) for g, at in gaps]}; longest "
        f"first tokens {[(round(g * 1e3, 1), round(at, 2)) for g, at in firsts]}")


def result_line(run: dict, entries: list, traced: bool) -> dict:
    sent = run.get("sent")
    if sent is not None:
        attempted = len(sent)
        failed = sum(1 for s in sent if not s.ok)
    else:
        attempted, failed = int(run["steps"]), 0
    dev = {k: run["device"][k] for k in
           ("platform", "kind", "count", "memory_peak_bytes")}
    line = dict(correct=bool(run["correct"]), attempted=attempted,
                failed=failed, metrics=read_metrics(entries, run),
                device=dev, check=run["check"],
                setup_phases=run["setup_phases"])
    if traced:
        tr = run["trace"]
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        line["breakdown"] = dict(device_ops=tr["device_ops"],
                                 idle_gaps=tr["idle_gaps"])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, config = find_cell(bench, args.workload)
    run = run_cell(
        workload=cell["name"], config_file=ROOT / config["file"],
        traffic_file=BENCH / "traffic" / f"{cell['traffic']}.json",
        cell_file=BENCH / "cells" / f"{cell['name']}.json",
        chips=int(cell["chips"]), seed=args.seed, seconds=args.seconds,
        traced=bool(args.trace), t_start=_T_IMPORT)
    entries = metrics_for(bench, cell["name"], bool(args.trace))
    print(json.dumps(result_line(run, entries, bool(args.trace))),
          flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    # the program's loader and serve threads are daemons; leave now
    os._exit(rc)
