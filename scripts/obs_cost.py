#!/usr/bin/env python
"""Abacus showback: the per-tenant chargeback report (obs/meter.py).

Reads the JSONL metrics stream a metered serving run wrote
(``TPUNN_METER=1`` + a ``metrics=`` sink: ``meter_ledger`` records,
one per tenant per summary flush — last-per-tenant wins, so a stream
with many flushes still renders the final ledgers) and prints the
showback table: per-tenant FLOPs, KV block-seconds, streamed wire
bytes, queue/decode wall time, tokens — and, under ``--price``, the
dollars each tenant owes plus their cost per 1k generated tokens.

The prefix-cache savings line is the counterfactual bill: FLOPs/tokens
the engine did NOT recompute because an admission rode a cached
prefix, credited to the tenant whose request skipped the work.

Every number is an integer straight off the meter's ledgers (the
per-tenant rows sum to the totals row EXACTLY — obs/meter.py's
integer-ledger contract), and the report JSON is canonical
(``sort_keys``): rendering the same ledgers twice is byte-identical.

Usage:
    python scripts/obs_cost.py runs/metrics.jsonl            # table
    python scripts/obs_cost.py runs/metrics.jsonl --json     # canonical
    python scripts/obs_cost.py runs/metrics.jsonl --price 2.0
    python scripts/obs_cost.py --selftest                    # tier-1 gate

``--price`` is dollars per PFLOP (1e15 FLOPs) billed — a deliberately
simple linear tariff; the analytic FLOP counts are the stable unit,
the tariff is policy.

The ``--selftest`` drill (the tier-1 acceptance gate, run as a
subprocess smoke by tests/test_quality.py) arms the meter, drives a
3-tenant mixed-prefix workload through a disaggregated fleet
(serve/disagg.py: every request crosses a prefill->decode handoff and
bills BOTH legs to its submitting tenant), and asserts the ledger
algebra: billed FLOPs reconcile with the analytic per-request counts
within 1%; per-tenant rows sum to the global totals exactly;
KV charges sum to the settle clock's wall witness exactly; the
rendered report is byte-identical across two renders.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.runtime.device import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

from pytorch_distributed_nn_tpu.obs.meter import (  # noqa: E402
    LEDGER_FIELDS,
    UNATTRIBUTED,
    ledger_totals,
)

PFLOP = 1e15


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


def ledgers_from_events(events: list[dict]) -> dict[str, dict[str, int]]:
    """Per-tenant ledgers from ``meter_ledger`` records, last-wins:
    the meter flushes cumulative ledgers at every summary boundary, so
    the newest record per tenant IS the final bill."""
    out: dict[str, dict[str, int]] = {}
    for e in events:
        if e.get("event") != "meter_ledger":
            continue
        tenant = str(e.get("tenant", UNATTRIBUTED))
        out[tenant] = {k: int(e.get(k, 0)) for k in LEDGER_FIELDS}
    return {t: out[t] for t in sorted(out)}


def build_report(ledgers: dict[str, dict[str, int]],
                 price_per_pflop: float = 0.0) -> dict:
    """The canonical report dict: per-tenant rows + exact totals +
    the savings credit, priced when a tariff is given. Pure in its
    inputs — same ledgers, same bytes (``to_json``)."""
    totals = ledger_totals(ledgers)
    report: dict = {"tenants": ledgers, "totals": totals}
    saved = {"tokens": totals["saved_tokens"],
             "flops": totals["saved_flops"]}
    if totals["flops"] + totals["saved_flops"] > 0:
        saved["billed_frac_avoided"] = round(
            totals["saved_flops"]
            / (totals["flops"] + totals["saved_flops"]), 6)
    report["savings"] = saved
    if price_per_pflop > 0:
        report["price_per_pflop"] = round(float(price_per_pflop), 6)
        cost = {}
        for tenant, led in ledgers.items():
            c = led["flops"] / PFLOP * price_per_pflop
            row = {"cost": round(c, 8)}
            if led["tokens"] > 0:
                row["cost_per_1k_tokens"] = round(
                    c * 1000.0 / led["tokens"], 8)
            cost[tenant] = row
        report["cost"] = cost
    return report


def to_json(report: dict) -> str:
    """Canonical bytes — the determinism unit the selftest asserts."""
    return json.dumps(report, sort_keys=True)


def render(report: dict) -> str:
    lines: list[str] = []
    out = lines.append
    tenants = report["tenants"]
    priced = "cost" in report
    out("== Abacus showback (obs/meter.py) ==")
    hdr = (f"{'tenant':>12} {'reqs':>5} {'tokens':>7} {'GFLOPs':>10} "
           f"{'kv_blk_s':>9} {'wire_MB':>8} {'queue_s':>8} "
           f"{'decode_s':>9}")
    if priced:
        hdr += f" {'$':>10} {'$/1k tok':>10}"
    out(hdr)
    rows = list(tenants.items()) + [("TOTAL", report["totals"])]
    for tenant, led in rows:
        row = (f"{tenant:>12} {led['requests']:>5} {led['tokens']:>7} "
               f"{led['flops'] / 1e9:>10.3f} "
               f"{led['kv_block_us'] / 1e6:>9.3f} "
               f"{led['wire_bytes'] / 1e6:>8.3f} "
               f"{led['queue_us'] / 1e6:>8.3f} "
               f"{led['decode_us'] / 1e6:>9.3f}")
        if priced:
            c = (report["cost"].get(tenant, {}) if tenant != "TOTAL"
                 else {"cost": round(sum(
                     r["cost"] for r in report["cost"].values()), 8)})
            row += f" {c.get('cost', 0.0):>10.6f}"
            row += (f" {c['cost_per_1k_tokens']:>10.6f}"
                    if "cost_per_1k_tokens" in c else f" {'-':>10}")
        out(row)
    s = report["savings"]
    out(f"prefix-cache savings: {s['tokens']} token(s) / "
        f"{s['flops'] / 1e9:.3f} GFLOPs not recomputed"
        + (f" ({s['billed_frac_avoided']:.1%} of the counterfactual "
           f"bill)" if "billed_frac_avoided" in s else ""))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# --selftest: the tier-1 acceptance drill
# ---------------------------------------------------------------------------

def _selftest() -> int:
    import jax

    if not os.environ.get("JAX_PLATFORMS"):
        # a host-side drill: the environment was read when jax was
        # imported, so a default chosen here goes through the config
        jax.config.update("jax_platforms", "cpu")
    import tempfile

    import numpy as np

    from pytorch_distributed_nn_tpu import obs
    from pytorch_distributed_nn_tpu.obs import flight, meter
    from pytorch_distributed_nn_tpu.utils.metrics import MetricsLogger

    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    meter.reset()

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.serve.disagg import DisaggFleet

    vocab = 97
    model = get_model(ModelConfig(
        name="llama3_8b", compute_dtype="float32", dtype="float32",
        extra=dict(num_layers=2, d_model=64, num_heads=4,
                   num_kv_heads=2, mlp_dim=128, vocab_size=vocab),
    ))
    params = model.init(jax.random.key(1),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]

    tenants = ("acme", "globex", "initech")
    rng = np.random.default_rng(7)
    base = rng.integers(1, vocab, size=(8,)).astype(np.int32)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "metrics.jsonl")
        with MetricsLogger(path) as m:
            assert meter.maybe_init("1", metrics=m) is not None
            fleet = DisaggFleet(
                model, params, prefill=1, decode=2, max_slots=2,
                max_seq_len=64, block_size=4, max_queue=16, metrics=m)
            # mixed-prefix 3-tenant workload: evens share a warm
            # prefix (cache-savings path), odds are cold; every
            # request crosses the prefill->decode handoff
            tickets = []
            for i in range(6):
                tenant = tenants[i % 3]
                if i % 2 == 0:
                    tail = rng.integers(1, vocab,
                                        size=(4,)).astype(np.int32)
                    prompt = np.concatenate([base, tail])
                else:
                    prompt = rng.integers(
                        1, vocab, size=(6 + i,)).astype(np.int32)
                tickets.append(fleet.submit(prompt, 4, tenant=tenant))
                fleet.run_until_idle()  # serialize: warm prefixes land
            assert all(t.done.is_set() and t.ok for t in tickets), \
                "selftest workload did not complete"
            mi = meter.meter()
            # freeze the settle clock: cached-tier KV blocks outlive
            # the requests and keep accruing block-time, so the flush
            # below and the export afterwards must settle to the SAME
            # instant for the byte-identity check to be meaningful
            mi._clock = (lambda t=mi._clock(): t)
            summ = fleet.summary()  # flushes meter_ledger JSONL too
            assert "meter" in summ, "fleet summary lost the rollup"
            fpt = fleet.replicas[0].engine.flops_per_token()
            assert fpt > 0, "analytic cost model unavailable"

        ledgers = mi.export_ledgers()
        totals = ledger_totals(ledgers)

        # 1. per-tenant rows sum to the global totals EXACTLY
        for k in LEDGER_FIELDS:
            assert totals[k] == sum(led[k] for led in
                                    ledgers.values()), k

        # 2. disagg handoff attribution: both legs bill the submitting
        # tenant — nothing lands on "default", and every tenant paid
        assert "default" not in ledgers, ledgers.keys()
        for t in tenants:
            assert ledgers[t]["requests"] >= 2, (t, ledgers[t])
            assert ledgers[t]["flops"] > 0, (t, ledgers[t])

        # 3. FLOPs reconcile: round-boundary billing vs the analytic
        # per-request counts from the engines' serve_request records
        events = load_events(path)
        analytic = 0
        for e in events:
            if e.get("event") != "serve_request":
                continue
            prefilled = (int(e["prompt_len"])
                         - int(e.get("cached_tokens", 0)))
            analytic += (prefilled
                         + max(int(e["new_tokens"]) - 1, 0)) * fpt
        assert analytic > 0
        drift = abs(totals["flops"] - analytic) / analytic
        assert drift <= 0.01, (totals["flops"], analytic)

        # 4. refcount-weighted KV conservation: per-tenant block-us
        # charges sum to the settle clock's wall witness exactly
        assert totals["kv_block_us"] == mi._kv_wall_us, (
            totals["kv_block_us"], mi._kv_wall_us)

        # 5. the shared prefix actually produced a savings credit
        assert totals["saved_tokens"] > 0, "no prefix-cache credit"

        # 6. the JSONL feed round-trips to the same ledgers, and the
        # rendered report is byte-identical across two renders
        from_stream = ledgers_from_events(events)
        assert from_stream == ledgers, "meter_ledger stream drifted"
        r1 = to_json(build_report(from_stream, price_per_pflop=2.0))
        r2 = to_json(build_report(
            ledgers_from_events(load_events(path)),
            price_per_pflop=2.0))
        assert r1 == r2, "report is not deterministic"
        print(render(build_report(from_stream, price_per_pflop=2.0)))

    meter.reset()
    print("obs_cost selftest ok: "
          f"{len(ledgers)} tenant(s), {totals['flops']} FLOPs billed, "
          f"drift {drift:.5f}, {totals['saved_tokens']} token(s) saved")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("jsonl", nargs="?", default="",
                    help="metrics JSONL a metered run wrote "
                         "(meter_ledger records)")
    ap.add_argument("--price", type=float, default=0.0,
                    help="dollars per PFLOP billed (0 = unpriced)")
    ap.add_argument("--json", action="store_true",
                    help="print the canonical report JSON instead of "
                         "the table")
    ap.add_argument("--selftest", action="store_true",
                    help="run the 3-tenant disagg billing drill "
                         "(tier-1 acceptance gate)")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if not args.jsonl:
        ap.error("need a metrics JSONL path (or --selftest)")
    try:
        events = load_events(args.jsonl)
    except OSError as e:
        print(f"cannot read {args.jsonl}: {e}", file=sys.stderr)
        return 1
    ledgers = ledgers_from_events(events)
    if not ledgers:
        # a quiet report, not a failure: the stream simply ran with
        # the meter unarmed (or hasn't flushed a summary yet)
        print(f"no meter_ledger records in {args.jsonl} "
              f"(run with TPUNN_METER=1 and a metrics sink)")
        return 0
    report = build_report(ledgers, price_per_pflop=args.price)
    print(to_json(report) if args.json else render(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
