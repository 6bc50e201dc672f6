#!/usr/bin/env python
"""Watchtower console: replay or tail a run's JSONL through the
online detectors and render alerts + SLO burn rates.

Two modes over the same engine (:mod:`obs.watchtower`):

- **replay** (default): read the whole metrics JSONL a train/serve run
  wrote, feed every record through :func:`watchtower.events_from_jsonl`
  in recorded order, print the alert stream and the end-state summary.
  Detectors consume event time only, so replaying the same file twice
  prints byte-identical alerts — this is the post-mortem view;
- **--follow**: tail the file live (poll for appended lines), printing
  alerts as they fire — the "watch the run" view for a job writing
  ``--metrics-out`` on the same host.

A third mode audits Helm instead of the detectors:

- **--autoscale**: shadow-replay a recorded decision journal
  (``Autoscaler.journal_jsonl()``) through the REAL policy:
  every ``autoscale_decision`` record carries its spec, evidence, and
  pre-decision state, so :func:`serve.autoscale.replay_decision`
  re-derives the verdict standalone and any divergence from what the
  journal claims exits 1 — the "would Helm do that again?" audit.

Usage:
    python scripts/obs_watch.py runs/metrics.jsonl
    python scripts/obs_watch.py runs/metrics.jsonl --follow
    python scripts/obs_watch.py runs/metrics.jsonl \
        --spec ttft_slo_s=0.25:burn_threshold=4 --json
    python scripts/obs_watch.py runs/helm.jsonl --autoscale
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.obs import watchtower  # noqa: E402
from pytorch_distributed_nn_tpu.obs.registry import (  # noqa: E402
    get_registry,
)

_SEV_MARK = {watchtower.WARN: "WARN", watchtower.PAGE: "PAGE"}


def _render_alert(a: "watchtower.Alert") -> str:
    mark = _SEV_MARK.get(a.severity, a.severity)
    line = (f"[{mark}] t={a.t:.3f} {a.kind} "
            f"(value={a.value:g} threshold={a.threshold:g}) {a.detail}")
    if a.attribution:
        keys = {k: v for k, v in a.attribution.items()
                if k != "forensics"}
        if keys:
            line += f"  attribution={json.dumps(keys, sort_keys=True)}"
    return line


def _burn_gauges() -> dict[str, float]:
    flat = get_registry().snapshot()
    return {k: v for k, v in sorted(flat.items())
            if k.startswith("watchtower_burn_rate")}


def _print_summary(tower: "watchtower.Watchtower",
                   as_json: bool) -> None:
    summary = tower.summary()
    burns = _burn_gauges()
    if as_json:
        print(json.dumps({"summary": summary, "burn_rates": burns,
                          "alerts": [a.as_dict() for a in tower.alerts]},
                         sort_keys=True))
        return
    print("\n== watchtower summary ==")
    print(f"  alerts: {summary['alerts_total']} "
          f"({summary['pages']} pages)  by kind: {summary['by_kind']}")
    if summary["burns_active"]:
        print(f"  burning SLOs: {', '.join(summary['burns_active'])}")
    if summary["drifting_ranks"]:
        print(f"  drifting ranks: {summary['drifting_ranks']}")
    for key, val in burns.items():
        print(f"  {key} = {val:g}")


def _feed(tower: "watchtower.Watchtower", line: str,
          as_json: bool) -> None:
    line = line.strip()
    if not line:
        return
    try:
        rec = json.loads(line)
    except json.JSONDecodeError:
        return  # torn tail line from a live writer
    before = len(tower.alerts)
    for ev in watchtower.events_from_jsonl(rec):
        tower.observe(ev)
    for alert in tower.alerts[before:]:
        print(alert.as_json() if as_json else _render_alert(alert))


def _shadow_replay_autoscale(path: str, as_json: bool) -> int:
    """--autoscale: re-run every journaled decision through the real
    policy and diff the verdicts. Each record is self-contained (spec
    + evidence + pre-decision state), so no fleet, tower, or ordering
    is needed — a tampered or stale journal diverges record by record."""
    from pytorch_distributed_nn_tpu.serve import autoscale

    try:
        f = open(path)
    except OSError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    total = diverged = 0
    with f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line from a live writer
            if rec.get("event", "autoscale_decision") \
                    != "autoscale_decision":
                continue
            total += 1
            want = (rec.get("action"), rec.get("reason"),
                    rec.get("to_replicas"))
            try:
                got = autoscale.replay_decision(rec)
            except (KeyError, TypeError, ValueError) as e:
                got = ("unreplayable", str(e), None)
            ok = got == want
            diverged += not ok
            if as_json:
                print(json.dumps(
                    {"seq": rec.get("seq"), "t": rec.get("t"),
                     "journaled": list(want), "replayed": list(got),
                     "ok": ok}, sort_keys=True))
            elif not ok:
                print(f"DIVERGED seq={rec.get('seq')} "
                      f"t={rec.get('t')}: journal says "
                      f"{want[0]}->{want[2]} ({want[1]}), policy "
                      f"says {got[0]}->{got[2]} ({got[1]})")
    verdict = {"decisions": total, "diverged": diverged,
               "ok": diverged == 0 and total > 0}
    if as_json:
        print(json.dumps({"autoscale_shadow": verdict},
                         sort_keys=True))
    else:
        print(f"\n== autoscale shadow replay ==\n  {total} decisions "
              f"re-derived, {diverged} diverged"
              + ("" if total else " (no autoscale_decision records)"))
    return 0 if verdict["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(
        description="replay/tail a metrics JSONL through the watchtower")
    ap.add_argument("metrics", help="JSONL metrics file from a run")
    ap.add_argument("--spec", default="1",
                    help="TPUNN_WATCH-style detector spec "
                         "(default: the stock thresholds)")
    ap.add_argument("--follow", action="store_true",
                    help="tail the file live instead of replaying once")
    ap.add_argument("--poll-s", type=float, default=0.5,
                    help="tail poll interval with --follow")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (alert JSON lines + "
                         "one summary object)")
    ap.add_argument("--autoscale", action="store_true",
                    help="shadow-replay a recorded Helm decision "
                         "journal through the real policy; exit 1 on "
                         "any divergence")
    args = ap.parse_args()

    if args.autoscale:
        return _shadow_replay_autoscale(args.metrics, args.json)

    tower = watchtower.Watchtower(watchtower.parse_spec(args.spec),
                                  dump_on_page=False)
    try:
        f = open(args.metrics)
    except OSError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    with f:
        for line in f:
            _feed(tower, line, args.json)
        if args.follow:
            try:
                while True:
                    line = f.readline()
                    if line:
                        _feed(tower, line, args.json)
                    else:
                        time.sleep(args.poll_s)
            except KeyboardInterrupt:
                pass
    _print_summary(tower, args.json)
    return 1 if tower.summary()["pages"] else 0


if __name__ == "__main__":
    sys.exit(main())
