"""Per-op device-time breakdown of a preset's train step, from a
perfetto trace (the r4/r5 ResNet MFU analyses are built on this).

Usage: python scripts/trace_ops.py --preset resnet50_dp \
           --set 'model.extra={"stem":"s2d"}' [--steps 10] [--top 30]

Prints the device-side op-name buckets (fusion kinds) sorted by total
time, normalized per step, plus the all-op total (= device ms/step).
"""

from __future__ import annotations

import argparse
import gzip
import json
import glob
import os
import re
import shutil
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, ".")

from pytorch_distributed_nn_tpu.runtime.device import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import jax  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="resnet50_dp")
    ap.add_argument("--set", action="append", default=[],
                    dest="overrides")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--per-chip-batch", type=int, default=0)
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--keep", default="",
                    help="keep the trace dir at this path")
    ap.add_argument("--full", action="store_true",
                    help="also print the top individual op names")
    args = ap.parse_args()

    from pytorch_distributed_nn_tpu.config import get_config, \
        parse_overrides
    from pytorch_distributed_nn_tpu.train.trainer import Trainer
    from pytorch_distributed_nn_tpu.utils.profiling import xprof_trace

    import bench

    overrides = parse_overrides(["--" + kv for kv in args.overrides])
    cfg = get_config(args.preset, **overrides)
    per_chip = (args.per_chip_batch
                or bench.PER_CHIP_BATCH.get(args.preset, 8))
    n_chips = len(jax.devices())
    cfg.data.batch_size = per_chip * n_chips
    cfg.steps = args.warmup + args.steps + 1
    cfg.log_every = 0
    trainer = Trainer(cfg)
    batch = trainer.loader.batch_at(0)
    state = trainer.state
    for _ in range(args.warmup):
        state, m = trainer.step_fn(state, *batch)
    float(jax.device_get(m["loss"]))

    trace_dir = args.keep or tempfile.mkdtemp(prefix="trace_ops_")
    with xprof_trace(trace_dir, perfetto=True):
        for _ in range(args.steps):
            state, m = trainer.step_fn(state, *batch)
        float(jax.device_get(m["loss"]))

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "perfetto_trace.json.gz"),
        recursive=True))
    if not paths:
        raise SystemExit(f"no perfetto trace under {trace_dir}")
    with gzip.open(paths[-1]) as f:
        tr = json.load(f)
    events = tr["traceEvents"] if isinstance(tr, dict) else tr

    # device-side op slices live on "XLA Ops" / TensorCore tracks; skip
    # python/host slices ($...), step markers, and async 'end:' pairs
    pid_names = {}
    tid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tid_names[(e["pid"], e["tid"])] = e["args"].get("name", "")

    device_tids = {k for k, v in tid_names.items()
                   if "XLA Ops" in v or "TensorCore" in v}
    buckets = defaultdict(float)
    total_us = 0.0
    n = 0
    for e in events:
        if e.get("ph") != "X":
            continue
        if device_tids and (e.get("pid"), e.get("tid")) not in device_tids:
            continue
        name = e.get("name", "")
        if name.startswith("$") or name.startswith("end: "):
            continue
        kind = re.sub(r"[.\d]+(\.clone)?$", "", name)
        dur = float(e.get("dur", 0.0))
        buckets[kind] += dur
        total_us += dur
        n += 1
    if not device_tids:
        print("NOTE: no 'XLA Ops' thread found; aggregated all X slices")
    per_step = total_us / args.steps / 1e3
    print(f"\ndevice ops: {n} slices, {per_step:.2f} ms/step total")
    print(f"{'bucket':44s} {'ms/step':>9s} {'%':>6s}")
    for kind, us in sorted(buckets.items(), key=lambda kv: -kv[1])[
            :args.top]:
        print(f"{kind:44s} {us/args.steps/1e3:9.3f} "
              f"{us/total_us*100:6.1f}")
    if args.full:
        full = defaultdict(float)
        for e in events:
            if e.get("ph") != "X":
                continue
            if device_tids and (e.get("pid"),
                                e.get("tid")) not in device_tids:
                continue
            name = e.get("name", "")
            if name.startswith("$") or name.startswith("end: "):
                continue
            full[name] += float(e.get("dur", 0.0))
        print(f"\ntop {args.top} individual ops:")
        for name, us in sorted(full.items(), key=lambda kv: -kv[1])[
                :args.top]:
            print(f"{name:58s} {us/args.steps/1e3:9.3f}")
    if not args.keep:
        shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
