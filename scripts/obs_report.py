#!/usr/bin/env python
"""Render the obs telemetry stream: step-time goodput breakdown + comms.

Reads the JSONL metrics file a training run wrote
(``TrainConfig.metrics_path`` — ``train_step`` / ``goodput`` /
``goodput_summary`` / ``eval`` events) and prints:

- the per-phase goodput table (seconds and share of wall, per logged
  window and whole-run);
- the comms cross-check: recorded wire bytes per step
  (ops/collectives.CommRecorder, carried in the goodput events) against
  trace-derived collective seconds when an xprof trace dir is given
  (``--trace``), yielding implied bus bandwidth;
- the train/eval metric tail.

- the xray capture section (``--xray DIR``): per-op attribution tables
  from any anomaly-triggered ``obs.xray`` captures under that
  directory (see ``scripts/obs_xray.py`` for the standalone renderer).

Usage:
    python scripts/obs_report.py runs/metrics.jsonl [--trace runs/xprof]
        [--xray runs/obs] [--last N]
"""

from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.obs.stats import percentile  # noqa: E402

PHASES = ("data", "compute", "collective", "checkpoint", "eval", "other")


def load_events(path: str) -> list[dict]:
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # tolerate a torn tail line from a killed run
    return events


def _fmt_s(v: float) -> str:
    return f"{v:10.4f}"


def _fmt_pct(v: float) -> str:
    return f"{100.0 * v:6.1f}%"


def print_goodput_table(events: list[dict], last: int,
                        quiet: bool = False) -> bool:
    windows = [e for e in events if e.get("event") == "goodput"]
    summary = next((e for e in events
                    if e.get("event") == "goodput_summary"), None)
    if not windows and summary is None:
        if not quiet:  # a serving-only file is not a broken train run
            print("no goodput events found (run with cfg.metrics_path "
                  "set)")
        return False
    header = (f"{'window@step':>12} {'steps':>5} {'wall_s':>10} "
              + " ".join(f"{p:>10}" for p in PHASES)
              + f" {'acct':>7}")
    print("== goodput breakdown (seconds; share of wall below) ==")
    print(header)
    for e in windows[-last:]:
        wall = _num(e, "wall_s")
        row = (f"{int(_num(e, 'step', -1)):>12} "
               f"{int(_num(e, 'steps', 1)):>5} "
               + _fmt_s(wall) + " "
               + " ".join(_fmt_s(_num(e, f'{p}_s')) for p in PHASES)
               + f" {_fmt_pct(_num(e, 'accounted_frac')):>7}")
        print(row)
        if wall > 0:
            print(f"{'':>12} {'':>5} {'':>10} "
                  + " ".join(
                      f"{_fmt_pct(_num(e, f'{p}_s') / wall):>10}"
                      for p in PHASES))
    if summary is not None:
        wall = _num(summary, "wall_s")
        print("-- whole run --")
        print(f"{'total':>12} {int(_num(summary, 'steps')):>5} "
              + _fmt_s(wall) + " "
              + " ".join(_fmt_s(_num(summary, f'{p}_s'))
                         for p in PHASES)
              + f" {_fmt_pct(_num(summary, 'accounted_frac')):>7}")
        if wall > 0:
            print(f"{'':>12} {'':>5} {'':>10} "
                  + " ".join(
                      f"{_fmt_pct(_num(summary, f'{p}_s') / wall):>10}"
                      for p in PHASES))
        print(f"goodput (compute+collective share of wall): "
              f"{_fmt_pct(_num(summary, 'goodput_frac')).strip()}")
    return True


def print_comms_table(events: list[dict], trace_dir: str | None) -> None:
    wire = None
    for e in events:
        if e.get("event") in ("goodput", "goodput_summary"):
            wire = e.get("wire_bytes_per_step", wire)
    summary = next((e for e in events
                    if e.get("event") == "goodput_summary"), None)
    if wire is None and trace_dir is None:
        return
    print("\n== comms ==")
    if wire is not None:
        print(f"recorded wire bytes/step (ring accounting): "
              f"{wire / 1e6:.3f} MB")
    ct = None
    if trace_dir:
        from pytorch_distributed_nn_tpu.utils.profiling import (
            collective_trace_seconds,
        )

        import jax

        world = len(jax.devices())
        ct = collective_trace_seconds(trace_dir, world=world)
        if ct is None:
            print(f"no collective slices found under {trace_dir}")
        else:
            print(f"trace-derived collective time: {ct.total_s:.4f}s "
                  f"total / {ct.per_device_s:.4f}s per device "
                  f"({ct.n_events} events)")
            for name, secs in sorted(ct.names.items(),
                                     key=lambda kv: -kv[1])[:8]:
                print(f"    {name:<40} {secs:.4f}s")
    if wire is not None and ct is not None and summary is not None:
        steps = max(summary.get("steps", 1), 1)
        coll_s = ct.per_device_s / steps
        if coll_s > 0:
            print(f"implied bus bandwidth (wire/step ÷ collective "
                  f"s/step): {wire / coll_s / 1e9:.3f} GB/s")


def _num(e: dict, key: str, default: float = 0.0) -> float:
    """Field access that tolerates a torn/partial record from a killed
    run (missing keys, JSON nulls) instead of TypeError-ing mid-table."""
    v = e.get(key, default)
    try:
        return float(v)
    except (TypeError, ValueError):
        return default


def print_metric_tail(events: list[dict], last: int) -> None:
    steps = [e for e in events if e.get("event") == "train_step"]
    evals = [e for e in events if e.get("event") == "eval"]
    if steps:
        print("\n== train tail ==")
        for e in steps[-last:]:
            print(f"step {int(_num(e, 'step', -1)):>6}  "
                  f"loss {_num(e, 'loss'):.4f}  "
                  f"{_num(e, 'samples_per_sec'):>10.1f} samples/s")
    if evals:
        print("== eval tail ==")
        for e in evals[-last:]:
            print(f"step {int(_num(e, 'step', -1)):>6}  "
                  f"loss {_num(e, 'loss'):.4f}  "
                  f"acc {_num(e, 'accuracy'):.4f}")


def _print_tenant_rows(reqs: list[dict], rejects: list[dict]) -> None:
    """Per-tenant breakdown of the serving section (Mosaic). One row
    per tenant: completed requests, tokens out, prefix-cache hit rate
    (``cached_tokens`` over prompt tokens), TTFT p50/p95, and rejects
    split into quota (reason ``tenant_quota``) vs shed (everything
    else). Skipped when the run is single-tenant with no rejects —
    the global percentiles above already tell that story."""
    per: dict[str, list[dict]] = {}
    for e in reqs:
        per.setdefault(str(e.get("tenant", "default")), []).append(e)
    rej: dict[str, list[dict]] = {}
    for e in rejects:
        rej.setdefault(str(e.get("tenant", "default")), []).append(e)
    tenants = sorted(set(per) | set(rej))
    if len(tenants) <= 1 and not rejects:
        return
    print("-- per tenant --")
    print(f"{'tenant':>12} {'reqs':>5} {'tokens':>7} {'hit':>6} "
          f"{'ttft_p50':>10} {'ttft_p95':>10} {'quota':>6} {'shed':>5}")
    for name in tenants:
        rs = per.get(name, [])
        ttft = [_num(e, "ttft_s") for e in rs]
        toks = sum(int(_num(e, "new_tokens")) for e in rs)
        prompt = sum(int(_num(e, "prompt_len")) for e in rs)
        cached = sum(int(_num(e, "cached_tokens")) for e in rs)
        hit = _fmt_pct(cached / prompt).strip() if prompt else "-"
        quota = sum(1 for e in rej.get(name, [])
                    if e.get("reason") == "tenant_quota")
        shed = len(rej.get(name, [])) - quota
        print(f"{name:>12} {len(rs):>5} {toks:>7} {hit:>6} "
              f"{_fmt_s(percentile(ttft, 0.50)) if rs else '         -'} "
              f"{_fmt_s(percentile(ttft, 0.95)) if rs else '         -'} "
              f"{quota:>6} {shed:>5}")


def print_serving_table(events: list[dict], last: int) -> bool:
    """Serving SLO section: per-request TTFT / per-token latency
    percentiles from ``serve_request`` events (scripts/serve.py
    --metrics-out), the per-tenant breakdown (Mosaic: TTFT, prefix-cache
    hit rate from ``cached_tokens``, quota rejects from ``serve_reject``
    events), plus the run-level ``serve_summary`` line. Silently
    skipped when the file has no serving events (training-only runs)."""
    reqs = [e for e in events if e.get("event") == "serve_request"]
    rejects = [e for e in events if e.get("event") == "serve_reject"]
    summary = next((e for e in reversed(events)
                    if e.get("event") == "serve_summary"), None)
    if not reqs and summary is None:
        return False

    print("\n== serving ==")
    if reqs:
        ttft = [_num(e, "ttft_s") for e in reqs]
        ptok = [_num(e, "per_token_s") for e in reqs]
        total = [_num(e, "total_s") for e in reqs]
        toks = sum(int(_num(e, "new_tokens")) for e in reqs)
        print(f"completed requests: {len(reqs)}  tokens out: {toks}")
        print(f"{'':>14} {'p50':>10} {'p95':>10} {'p99':>10}")
        for name, xs in (("ttft_s", ttft), ("per_token_s", ptok),
                         ("total_s", total)):
            print(f"{name:>14} {_fmt_s(percentile(xs, 0.50))} "
                  f"{_fmt_s(percentile(xs, 0.95))} "
                  f"{_fmt_s(percentile(xs, 0.99))}")
        kv = [_num(e, "kv_util") for e in reqs if "kv_util" in e]
        if kv:
            print(f"KV-pool utilization at retire: mean "
                  f"{_fmt_pct(sum(kv) / len(kv)).strip()}, peak "
                  f"{_fmt_pct(max(kv)).strip()}")
        _print_tenant_rows(reqs, rejects)
        print("-- request tail --")
        for e in reqs[-last:]:
            print(f"  {e.get('request_id', '?'):>8}  "
                  f"prompt {int(_num(e, 'prompt_len')):>4}  "
                  f"+{int(_num(e, 'new_tokens')):>3} tok  "
                  f"ttft {_num(e, 'ttft_s') * 1e3:8.2f}ms  "
                  f"tok {_num(e, 'per_token_s') * 1e3:8.3f}ms")
    if summary is not None:
        print("-- run summary --")
        print(f"  requests {int(_num(summary, 'requests'))} "
              f"(completed {int(_num(summary, 'completed'))}, "
              f"rejected {int(_num(summary, 'rejected'))})  "
              f"{_num(summary, 'tokens_per_s'):.1f} tokens/s  "
              f"occupancy {_fmt_pct(_num(summary, 'occupancy')).strip()}  "
              f"kv_util {_fmt_pct(_num(summary, 'kv_util')).strip()}")
        reasons = summary.get("reject_reasons") or {}
        if isinstance(reasons, dict) and reasons:
            why = ", ".join(f"{k}={v}" for k, v in sorted(reasons.items()))
            print(f"  reject reasons: {why}")
    return True


def print_fleet_table(events: list[dict], last: int) -> bool:
    """Replica-fleet section (serve/fleet.py): per-replica occupancy
    from the ``replica`` tag on ``serve_request`` records, replica
    deaths with their stranded requests, failover re-admission latency
    percentiles, and rolling reloads. Silently skipped when the file
    has no fleet events (single-engine and training runs)."""
    downs = [e for e in events if e.get("event") == "fleet_replica_down"]
    fos = [e for e in events if e.get("event") == "fleet_failover"]
    states = [e for e in events if e.get("event") == "fleet_state"]
    reloads = [e for e in events if e.get("event") == "fleet_reload"]
    hoffs = [e for e in events if e.get("event") == "fleet_handoff"]
    xfers = [e for e in events if e.get("event") == "kv_transfer"]
    tagged = [e for e in events
              if e.get("event") == "serve_request" and e.get("replica")]
    if not (downs or fos or states or reloads or hoffs or xfers):
        return False

    print("\n== fleet ==")
    if tagged:
        per: dict[str, list[dict]] = {}
        for e in tagged:
            per.setdefault(str(e.get("replica")), []).append(e)
        print(f"{'replica':>8} {'requests':>9} {'tokens':>8} "
              f"{'ttft_p50':>10} {'ttft_p99':>10}")
        for name in sorted(per):
            rs = per[name]
            ttft = [_num(e, "ttft_s") for e in rs]
            toks = sum(int(_num(e, "new_tokens")) for e in rs)
            print(f"{name:>8} {len(rs):>9} {toks:>8} "
                  f"{_fmt_s(percentile(ttft, 0.50))} "
                  f"{_fmt_s(percentile(ttft, 0.99))}")
    if downs:
        print(f"replica deaths: {len(downs)}")
        for e in downs[-last:]:
            stranded = e.get("stranded") or []
            ids = ", ".join(str(s) for s in stranded) or "(none)"
            print(f"  replica {int(_num(e, 'replica', -1))} DOWN "
                  f"({e.get('reason', '?')}) — stranded: {ids}")
    if fos:
        lat = [_num(e, "readmit_s") for e in fos]
        print(f"failovers: {len(fos)}  re-admission latency "
              f"p50 {percentile(lat, 0.50) * 1e3:.2f}ms  "
              f"p99 {percentile(lat, 0.99) * 1e3:.2f}ms")
        for e in fos[-last:]:
            print(f"  {e.get('request_id', '?'):>8}  "
                  f"r{int(_num(e, 'from_replica', -1))}"
                  f"->r{int(_num(e, 'to_replica', -1))}  "
                  f"prefix {int(_num(e, 'prefix_tokens')):>3} tok  "
                  f"readmit {_num(e, 'readmit_s') * 1e3:8.2f}ms")
    if hoffs:
        # disaggregated fleet (serve/disagg.py): prefill->decode
        # handoffs and the KV block streams that warm them
        pfx = [_num(e, "prefix_tokens") for e in hoffs]
        print(f"prefill->decode handoffs: {len(hoffs)}  "
              f"stitched prefix p50 {percentile(pfx, 0.50):.0f} tok  "
              f"p99 {percentile(pfx, 0.99):.0f} tok")
    if xfers:
        n_ok = sum(1 for e in xfers if e.get("outcome") == "ok")
        failed = [e for e in xfers if e.get("outcome") == "failed"]
        total_b = sum(_num(e, "bytes") for e in xfers)
        print(f"kv transfers: {len(xfers)} ({n_ok} ok, "
              f"{len(failed)} failed)  "
              f"{total_b / 1e6:.2f} MB streamed")
        for e in failed[-last:]:
            print(f"  r{int(_num(e, 'src', -1))}"
                  f"->r{int(_num(e, 'dst', -1))} FAILED mid-transfer "
                  f"({int(_num(e, 'blocks'))} blocks)")
    if reloads:
        rolled = sum(int(_num(e, "replicas")) for e in reloads)
        print(f"rolling reloads: {len(reloads)} "
              f"({rolled} replica(s) rolled)")
    return True


def print_trace_table(events: list[dict], last: int) -> bool:
    """Causeway section (obs/trace.py): per-segment latency
    percentiles across every traced request in the stream, plus the
    dominant-segment table — for each trace, which segment owned the
    most critical-path time (obs/critpath.py attribution). Silently
    skipped when the file has no ``trace_span`` events (TPUNN_TRACE
    unset). Full waterfalls: ``scripts/obs_trace.py`` on this file."""
    spans = [{k: v for k, v in e.items()
              if k not in ("event", "time", "process")}
             for e in events if e.get("event") == "trace_span"]
    if not spans:
        return False
    from pytorch_distributed_nn_tpu.obs import critpath

    print("\n== request traces (Causeway) ==")
    durs = [s for s in spans
            if s.get("segment") in critpath.PRIORITY
            and _num(s, "t1") > _num(s, "t0")]
    per_seg: dict[str, list[float]] = {}
    for s in durs:
        per_seg.setdefault(str(s["segment"]), []).append(
            _num(s, "t1") - _num(s, "t0"))
    traces = sorted({str(s.get("trace", "")) for s in spans})
    print(f"{len(traces)} trace(s), {len(spans)} span(s)")
    if per_seg:
        print(f"{'segment':>9} {'spans':>6} {'p50':>10} {'p99':>10}")
        for seg in sorted(per_seg,
                          key=lambda k: -critpath.PRIORITY[k]):
            xs = per_seg[seg]
            print(f"{seg:>9} {len(xs):>6} "
                  f"{_fmt_s(percentile(xs, 0.50))} "
                  f"{_fmt_s(percentile(xs, 0.99))}")
    dominated: dict[str, int] = {}
    worst: list[tuple[float, str, str]] = []
    for t in traces:
        cp = critpath.critical_path(
            [s for s in spans if str(s.get("trace", "")) == t])
        if not cp["segments"]:
            continue
        dominated[cp["dominant"]] = dominated.get(cp["dominant"], 0) + 1
        worst.append((cp["total_s"], t, cp["dominant"]))
    if dominated:
        print("dominant segment: " + ", ".join(
            f"{seg} x{n}" for seg, n in
            sorted(dominated.items(), key=lambda kv: -kv[1])))
    for total, t, dom in sorted(worst, reverse=True)[:last]:
        print(f"  {t}  {total * 1e3:8.1f}ms  dominated by {dom}")
    return True


def print_cost_table(events: list[dict], last: int) -> bool:
    """Abacus section (obs/meter.py): the per-tenant resource bill —
    FLOPs, KV block-seconds, wire bytes, queue/decode wall time —
    from the ``meter_ledger`` records the meter flushes at every
    summary boundary (cumulative, so last-per-tenant wins), plus the
    costliest individual requests from the ``meter_request`` tail.
    Silently skipped when the file has no ledger records (TPUNN_METER
    unset). Pricing + the full showback: ``scripts/obs_cost.py``."""
    from pytorch_distributed_nn_tpu.obs.meter import (
        LEDGER_FIELDS, UNATTRIBUTED, ledger_totals)
    ledgers: dict[str, dict[str, int]] = {}
    for e in events:
        if e.get("event") != "meter_ledger":
            continue
        ledgers[str(e.get("tenant", UNATTRIBUTED))] = {
            k: int(e.get(k, 0)) for k in LEDGER_FIELDS}
    if not ledgers:
        return False
    print("\n== tenant billing (Abacus) ==")
    print(f"{'tenant':>12} {'reqs':>5} {'tokens':>7} {'GFLOPs':>10} "
          f"{'kv_blk_s':>9} {'wire_MB':>8} {'decode_s':>9}")
    rows = sorted(ledgers.items(),
                  key=lambda kv: -kv[1]["flops"])
    totals = ledger_totals(ledgers)
    for tenant, led in rows + [("TOTAL", totals)]:
        print(f"{tenant:>12} {led['requests']:>5} {led['tokens']:>7} "
              f"{led['flops'] / 1e9:>10.3f} "
              f"{led['kv_block_us'] / 1e6:>9.3f} "
              f"{led['wire_bytes'] / 1e6:>8.3f} "
              f"{led['decode_us'] / 1e6:>9.3f}")
    if totals["saved_tokens"]:
        print(f"prefix-cache savings: {totals['saved_tokens']} "
              f"token(s) / {totals['saved_flops'] / 1e9:.3f} GFLOPs "
              f"not recomputed")
    reqs = [e for e in events if e.get("event") == "meter_request"]
    for e in sorted(reqs, key=lambda e: -_num(e, "flops"))[:last]:
        print(f"  {e.get('tenant', UNATTRIBUTED):>12} "
              f"{str(e.get('request_id', '')):>8} "
              f"{_num(e, 'flops') / 1e9:10.3f} GFLOPs "
              f"{int(_num(e, 'tokens'))} token(s)")
    return True


def print_audit_table(events: list[dict], last: int) -> bool:
    """Lighthouse section (obs/audit.py): output-integrity coverage —
    how many ``serve_request`` records carry a fingerprint chain,
    every confirmed divergence with its replica pair and suspect,
    golden-probe pass/fail tallies, and quarantined replicas with the
    work re-admitted off them. Silently skipped when the file has no
    audit events (TPUNN_AUDIT unset). The standalone report + the
    tier-1 corruption drill: ``scripts/obs_audit.py``."""
    reqs = [e for e in events if e.get("event") == "serve_request"]
    fps = [e for e in reqs if e.get("fp")]
    divs = [e for e in events if e.get("event") == "audit_divergence"]
    probes = [e for e in events if e.get("event") == "audit_probe"]
    quars = [e for e in events if e.get("event") == "fleet_quarantine"]
    if not (fps or divs or probes or quars):
        return False
    print("\n== output integrity (Lighthouse) ==")
    if reqs:
        print(f"fingerprints: {len(fps)} of {len(reqs)} "
              f"request record(s) carry a token chain")
    if probes:
        failed = sum(1 for e in probes if not int(_num(e, "ok", 1)))
        print(f"golden probes: {len(probes)} ({failed} failed)")
    if divs:
        print(f"divergences: {len(divs)} confirmed")
        for e in divs[-last:]:
            pair = ",".join(str(p) for p in e.get("pair") or [])
            print(f"  {e.get('kind', '?'):>8} "
                  f"{str(e.get('request_id', '')):>10} "
                  f"pair={pair or '-'} suspect={e.get('suspect', '?')}")
    for e in quars[-last:]:
        stranded = e.get("stranded") or []
        ids = ", ".join(str(s) for s in stranded) or "(none)"
        print(f"quarantined: replica {int(_num(e, 'replica', -1))} "
              f"({e.get('reason', '?')}) — re-admitted: {ids}")
    return True


def print_capacity_table(events: list[dict], last: int,
                         requested: bool = False) -> bool:
    """Skyline capacity-planning section (obs/capacity.py): the
    offered-load rung table per replica count with each SLO class's
    verdict, the sustainable frontier + goodput knee, the "replicas
    needed per SLO" plan line, and — under a chaos drill — the failover
    windows that moved the frontier. Silently skipped when the file has
    no ``capacity_*`` events unless ``--capacity`` asked for it."""
    rungs = [e for e in events if e.get("event") == "capacity_rung"]
    fronts = [e for e in events
              if e.get("event") == "capacity_frontier"]
    plan = next((e for e in reversed(events)
                 if e.get("event") == "capacity_plan"), None)
    if not (rungs or fronts or plan):
        if requested:
            print("\nno capacity events found (write a plan's "
                  "obs.capacity.report_events, one JSON line each)")
        return False

    print("\n== capacity frontier (Skyline) ==")
    if plan is not None:
        line = (f"shape {plan.get('shape', '?')}  "
                f"seed {int(_num(plan, 'seed'))}")
        if plan.get("chaos"):
            line += f"  chaos {plan['chaos']}"
        print(line)
        print(f"  spec: {plan.get('spec', '?')}")
    slo_names = sorted({name for e in rungs
                        for name in (e.get("slo") or {})})
    if rungs:
        print(f"{'replicas':>8} {'offered':>9} {'goodput':>9} "
              f"{'rej':>5} "
              + " ".join(f"{n:>16}" for n in slo_names))
        for e in rungs:  # a sweep is small; truncation hides the knee
            cells = []
            for name in slo_names:
                j = (e.get("slo") or {}).get(name) or {}
                tag = "ok" if j.get("sustainable") else "BURN"
                cells.append(f"{tag:>4} "
                             f"{_fmt_pct(_num(j, 'attainment')).strip():>6}"
                             f" p{int(_num(j, 'burn_pages')):<3}")
            print(f"{int(_num(e, 'replicas')):>8} "
                  f"{_num(e, 'offered_rps'):>9.2f} "
                  f"{_num(e, 'goodput_tps'):>9.1f} "
                  f"{int(_num(e, 'rejects')):>5} "
                  + " ".join(cells))
    for e in fronts:
        front = e.get("frontier") or {}
        parts = [f"{k} {v:.2f} req/s" if v is not None
                 else f"{k} none" for k, v in sorted(front.items())]
        knee = e.get("knee_rps")
        print(f"frontier @{int(_num(e, 'replicas'))} replica(s): "
              + ", ".join(parts)
              + (f"  (goodput knee {knee:.2f} rps)"
                 if knee is not None else "  (no saturation knee)"))
    wins = [(int(_num(e, "replicas")), w) for e in rungs
            for w in (e.get("failover_windows") or [])]
    if wins:
        print(f"failover windows (chaos drill): {len(wins)}")
        for n, w in wins[-last:]:
            rec = w.get("t_recovered")
            print(f"  @{n} replica(s): replica "
                  f"{int(_num(w, 'replica', -1))} down "
                  f"t={_num(w, 't_down'):.2f}s, "
                  f"{int(_num(w, 'readmitted'))} re-admitted, "
                  + (f"recovered t={rec:.2f}s" if rec is not None
                     else "no re-admissions to recover"))
    if plan is not None:
        needed = plan.get("replicas_needed") or {}
        for name in sorted(needed):
            d = needed[name] or {}
            n = d.get("replicas")
            print(f"replicas needed [{name}] for "
                  f"{_num(d, 'target_rps'):.2f} req/s: "
                  + (str(int(n)) if n is not None
                     else "none swept suffices"))
    return True


def print_autoscale_table(events: list[dict], last: int,
                          requested: bool = False) -> bool:
    """Helm section (serve/autoscale.py): the replica trajectory as
    the autoscaler steered it — every scale_up/scale_down with the
    journaled evidence that drove it (per-window burns, queue/KV
    fractions, forecast floor), plus the hold-reason tally. Silently
    skipped when the file has no ``autoscale_decision`` events unless
    ``--autoscale`` asked for it."""
    decs = [e for e in events
            if e.get("event") == "autoscale_decision"]
    if not decs:
        if requested:
            print("\nno autoscale decisions found (write each line "
                  "of Autoscaler.journal_jsonl() with "
                  "event=autoscale_decision added)")
        return False

    print("\n== autoscale decisions (Helm) ==")
    lastd = decs[-1]
    ev = lastd.get("evidence") or {}
    print(f"policy: {lastd.get('spec', '?')}")
    fc = ev.get("forecast_replicas")
    print(f"decisions {len(decs)}, final target "
          f"{int(_num(lastd, 'to_replicas'))}"
          + (f", Skyline forecast {int(fc)}" if fc is not None
             else ", no Skyline forecast"))
    holds: dict[str, int] = {}
    actions = []
    for d in decs:
        if d.get("action") == "hold":
            r = str(d.get("reason", "?"))
            holds[r] = holds.get(r, 0) + 1
        else:
            actions.append(d)
    if holds:
        print("holds: " + ", ".join(
            f"{k} x{v}" for k, v in sorted(holds.items())))
    if actions:
        print(f"{'t':>10} {'action':>10} {'replicas':>9} "
              f"{'burn f/s':>11} {'queue':>6} {'kv':>5}  reason")
        for d in actions:  # a trajectory is small; holds are tallied
            e = d.get("evidence") or {}
            burns = (e.get("burn") or {}).get("ttft") or {}
            print(f"{_num(d, 't'):>10.2f} {d.get('action', '?'):>10} "
                  f"{int(_num(d, 'from_replicas')):>4}->"
                  f"{int(_num(d, 'to_replicas')):<4} "
                  f"{_num(burns, 'fast'):>5.2f}/"
                  f"{_num(burns, 'slow'):<5.2f} "
                  f"{_fmt_pct(_num(e, 'queue_frac')).strip():>6} "
                  f"{_fmt_pct(_num(e, 'kv_free_frac')).strip():>5}"
                  f"  {d.get('reason', '?')}")
    else:
        print("no scale actions (steady)")
    return True


def print_xray_table(xray_dir: str | None, last: int) -> bool:
    """Xray section: per-op attribution from anomaly-triggered
    ``obs.xray`` captures under ``--xray DIR``. Silently skipped when
    no directory is given; noisy when one is given but holds no
    captures (the operator asked and should hear "nothing there")."""
    if not xray_dir:
        return False
    from pytorch_distributed_nn_tpu.obs import xray

    paths = xray.find_captures(xray_dir)
    if not paths:
        print(f"\nno xray captures under {xray_dir}")
        return False
    print("\n== xray captures ==")
    for p in paths[-last:]:
        try:
            summary = xray.load_capture(p)
        except (OSError, json.JSONDecodeError):
            print(f"  unreadable capture: {p}")
            continue
        att = summary.get("attribution") or {}
        print(f"-- {summary.get('reason', '?')} at step "
              f"{summary.get('trigger_step', -1)} "
              f"(source={att.get('source', 'none')}) --")
        table = xray.render_op_table(att, top=last)
        if table:
            print(table)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("jsonl", help="metrics JSONL path "
                                  "(TrainConfig.metrics_path)")
    ap.add_argument("--trace", default="",
                    help="xprof trace dir (perfetto_trace.json.gz) for "
                         "the trace-derived collective cross-check")
    ap.add_argument("--xray", default="",
                    help="directory holding obs.xray capture dirs "
                         "(xray_*/xray_summary.json) to render")
    ap.add_argument("--capacity", action="store_true",
                    help="insist on the Skyline capacity section "
                         "(noisy when the file has no capacity_* "
                         "events; auto-rendered when it does)")
    ap.add_argument("--autoscale", action="store_true",
                    help="insist on the Helm autoscale section "
                         "(noisy when the file has no "
                         "autoscale_decision events; auto-rendered "
                         "when it does)")
    ap.add_argument("--last", type=int, default=5,
                    help="windows/rows to show per table")
    args = ap.parse_args(argv)
    try:
        events = load_events(args.jsonl)
    except OSError as e:
        print(f"cannot read {args.jsonl}: {e}", file=sys.stderr)
        return 1
    if not events:
        # an empty or torn stream is a quiet report, not a crash —
        # monitoring wrappers run this before the workload has
        # emitted anything
        print(f"no events in {args.jsonl}")
        if args.xray:
            print_xray_table(args.xray, args.last)
        return 0
    has_serve = any(e.get("event") in
                    ("serve_request", "serve_summary", "fleet_state",
                     "fleet_replica_down", "fleet_failover",
                     "fleet_reload", "fleet_handoff", "kv_transfer",
                     "trace_span", "meter_ledger", "capacity_rung",
                     "capacity_frontier", "capacity_plan",
                     "autoscale_decision", "audit_divergence",
                     "audit_probe", "fleet_quarantine")
                    for e in events)
    ok = print_goodput_table(events, args.last, quiet=has_serve)
    print_comms_table(events, args.trace or None)
    serve_ok = print_serving_table(events, args.last)
    fleet_ok = print_fleet_table(events, args.last)
    trace_ok = print_trace_table(events, args.last)
    cost_ok = print_cost_table(events, args.last)
    audit_ok = print_audit_table(events, args.last)
    cap_ok = print_capacity_table(events, args.last,
                                  requested=args.capacity)
    helm_ok = print_autoscale_table(events, args.last,
                                    requested=args.autoscale)
    xray_ok = print_xray_table(args.xray or None, args.last)
    print_metric_tail(events, args.last)
    if not (ok or serve_ok or fleet_ok or trace_ok or cost_ok
            or audit_ok or cap_ok or helm_ok or xray_ok):
        print("nothing to report (no recognized event families)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
