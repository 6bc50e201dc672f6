#!/usr/bin/env python
"""Lighthouse integrity report: fingerprints, divergences, quarantines
(obs/audit.py).

Reads the JSONL metrics stream an audited serving run wrote
(``TPUNN_AUDIT=`` armed + a ``metrics=`` sink) and prints the output-
integrity picture: how many requests carry a token-fingerprint chain,
every confirmed divergence (shadow-replay mismatch, golden-probe
failure, worker chain break) with the replica pair and the suspect the
majority named, golden-probe pass/fail tallies, and which replicas were
quarantined — with the reason and how many in-flight requests were
re-admitted on survivors.

A stream with no audit activity renders a one-line quiet report and
exits 0 — absence of evidence is the healthy steady state, not an
error. Torn tail lines (a killed run) are tolerated.

Usage:
    python scripts/obs_audit.py runs/metrics.jsonl          # table
    python scripts/obs_audit.py runs/metrics.jsonl --json   # canonical
    python scripts/obs_audit.py --selftest                  # tier-1 gate

The ``--selftest`` drill (the tier-1 acceptance gate, run as a
subprocess smoke by tests/test_quality.py) is the end-to-end silent-
corruption story: an UNARMED baseline run over a 3-replica fleet
records the honest outputs (and proves the audit writes nothing — no
registry counters, no flight-ring events, no ``fp`` keys); then the
same workload runs with ``TPUNN_AUDIT=sample=1.0:quarantine=1`` armed
and ``flip@replica=1:step=3`` chaos corrupting one decoded token on
replica 1. The drill asserts the full chain reacted: a watchtower
``output_divergence`` page names r1 as the suspect, r1 lands in
QUARANTINED (through the counted ``_set_state`` choke point — router
excludes it, no restart is ever scheduled), the requests stranded on
r1 re-admit on the survivors (``failovers > 0``), and every final
client-visible token stream is BIT-IDENTICAL to the unarmed baseline
— the corruption never reached a caller.
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


def build_report(events: list[dict]) -> dict:
    """The canonical integrity report dict. Pure in its inputs — same
    events, same bytes (``to_json``)."""
    total = fingerprinted = 0
    divergences: list[dict] = []
    by_kind: dict[str, int] = {}
    probes = probe_failures = 0
    quarantines: list[dict] = []
    for e in events:
        ev = e.get("event")
        if ev == "serve_request":
            total += 1
            if e.get("fp"):
                fingerprinted += 1
        elif ev == "audit_divergence":
            rec = {"kind": str(e.get("kind", "")),
                   "request_id": str(e.get("request_id", "")),
                   "pair": [str(p) for p in e.get("pair", [])],
                   "suspect": str(e.get("suspect", ""))}
            divergences.append(rec)
            by_kind[rec["kind"]] = by_kind.get(rec["kind"], 0) + 1
        elif ev == "audit_probe":
            probes += 1
            if not int(e.get("ok", 1)):
                probe_failures += 1
        elif ev == "fleet_quarantine":
            stranded = e.get("stranded", [])
            quarantines.append({
                "replica": int(e.get("replica", -1)),
                "reason": str(e.get("reason", "")),
                "stranded": (len(stranded)
                             if isinstance(stranded, list)
                             else int(stranded))})
    return {
        "requests": {"total": total, "fingerprinted": fingerprinted},
        "divergences": divergences,
        "divergences_by_kind": {k: by_kind[k] for k in sorted(by_kind)},
        "probes": {"total": probes, "failed": probe_failures},
        "quarantines": quarantines,
    }


def is_quiet(report: dict) -> bool:
    """No audit activity at all — the healthy (or unarmed) stream."""
    return (report["requests"]["fingerprinted"] == 0
            and not report["divergences"]
            and report["probes"]["total"] == 0
            and not report["quarantines"])


def to_json(report: dict) -> str:
    """Canonical bytes — the determinism unit the selftest asserts."""
    return json.dumps(report, sort_keys=True)


def render(report: dict) -> str:
    lines: list[str] = []
    out = lines.append
    out("== Lighthouse output integrity (obs/audit.py) ==")
    r = report["requests"]
    out(f"fingerprints: {r['fingerprinted']} of {r['total']} "
        f"request record(s) carry a token chain")
    p = report["probes"]
    if p["total"]:
        out(f"golden probes: {p['total']} run, {p['failed']} failed")
    if report["divergences"]:
        out(f"divergences: {len(report['divergences'])} confirmed "
            + " ".join(f"{k}={n}" for k, n in
                       report["divergences_by_kind"].items()))
        for d in report["divergences"]:
            out(f"  {d['kind']:>8} {d['request_id'] or '(probe)':>20} "
                f"pair={','.join(d['pair'])} suspect={d['suspect']}")
    else:
        out("divergences: none")
    if report["quarantines"]:
        for q in report["quarantines"]:
            out(f"quarantined: replica {q['replica']} "
                f"({q['reason']}) — {q['stranded']} in-flight "
                f"request(s) re-admitted on survivors")
    else:
        out("quarantines: none")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# --selftest: the end-to-end silent-corruption drill (tier-1 gate)
# ---------------------------------------------------------------------------

def _run_workload(model, params, jobs, metrics=None):
    """One fleet pass over the canned workload (``jobs`` is a list of
    ``(request_id, prompt, budget)``); returns (per-ticket token
    lists, fleet). Greedy + seed-pinned: bit-reproducible."""
    from pytorch_distributed_nn_tpu.serve import Fleet

    fleet = Fleet(model, params, replicas=3, max_slots=2,
                  max_seq_len=96, block_size=16, metrics=metrics)
    tickets = [fleet.submit(p, b, request_id=rid)
               for rid, p, b in jobs]
    fleet.run_until_idle()
    outs = []
    for t in tickets:
        assert t.ok, (t.request_id, t.status, t.reject_reason)
        outs.append([int(x) for x in t.tokens])
    return outs, fleet


def _selftest() -> int:
    import jax

    if not os.environ.get("JAX_PLATFORMS"):
        # a host-side drill: the environment was read when jax was
        # imported, so a default chosen here goes through the config
        jax.config.update("jax_platforms", "cpu")
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_nn_tpu import obs
    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.obs import audit, flight, watchtower
    from pytorch_distributed_nn_tpu.runtime import chaos
    from pytorch_distributed_nn_tpu.serve.router import QUARANTINED
    from pytorch_distributed_nn_tpu.utils.metrics import MetricsLogger

    from pytorch_distributed_nn_tpu.models import get_model

    vocab = 97
    model = get_model(ModelConfig(
        name="llama3_8b", compute_dtype="float32", dtype="float32",
        extra=dict(num_layers=2, d_model=64, num_heads=4,
                   num_kv_heads=2, mlp_dim=128, vocab_size=vocab)))
    params = model.init(jax.random.key(1),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    rng = np.random.default_rng(7)
    # "lh-5" is the only request whose id hashes under sample=0.25,
    # so it alone grows a shadow leg (which the router places on r1,
    # where the chaos flip corrupts it).  It is short: its shadow
    # comparison settles while the unsampled long requests still
    # decode — which is what strands a real, journaled leg on r1 at
    # quarantine time and forces a failover re-admission.  Exactly
    # three long requests: one lands on each replica, which keeps a
    # slot free on r2 for the referee leg (a full r2 would queue the
    # referee behind 24-token decodes and settle the divergence only
    # after every real leg had already finished).
    short = rng.integers(1, vocab, size=(10,)).astype(np.int32)
    longs = [rng.integers(1, vocab, size=(n,)).astype(np.int32)
             for n in (12, 9, 14)]
    jobs = [("lh-5", short, 4)] + [
        (f"lh-{i}", p, 24) for i, p in enumerate(longs)]

    # -- unarmed baseline: the honest outputs, and proof of inertness --
    audit.reset()
    chaos.reset()
    watchtower.reset()
    obs.reset_registry()
    flight.reset_recorder(enabled=True)
    baseline, fleet0 = _run_workload(model, params, jobs)
    assert audit.summary() is None, "unarmed audit has state"
    assert audit.seed_of([1, 2]) == "", "unarmed seed_of not inert"
    assert not audit.shadow_sampled("lh-5"), "unarmed sample not inert"
    ring = [ev for ev in flight.get_recorder().snapshot()
            if ev["kind"] == "audit"]
    assert not ring, f"unarmed run wrote audit ring events: {ring}"
    assert all("fp" not in r for r in fleet0.completed), \
        "unarmed serve_request records carry fp keys"

    # -- armed run + chaos flip: the whole chain must react ------------
    audit.reset()
    chaos.reset()
    watchtower.reset()
    obs.reset_registry()
    flight.reset_recorder(enabled=True)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "metrics.jsonl")
        with MetricsLogger(path) as m:
            assert audit.maybe_init("sample=0.25:quarantine=1",
                                    rank=0, metrics=m) is not None
            assert audit.shadow_sampled("lh-5"), "lh-5 not in sample"
            assert not audit.shadow_sampled("lh-0"), "lh-0 in sample"
            chaos.maybe_init("flip@replica=1:step=3", rank=0, seed=0)
            watchtower.maybe_init("1", rank=0, metrics=m)
            armed, fleet = _run_workload(model, params, jobs,
                                         metrics=m)

            # 1. the corruption never reached a caller: every stream
            # bit-identical to the unarmed baseline
            assert armed == baseline, "outputs diverged from baseline"

            # 2. the page names replica 1 as the suspect
            tw = watchtower.tower()
            pages = [a for a in tw.alerts
                     if a.kind == "output_divergence"]
            assert pages, "no output_divergence page raised"
            assert any("r1" in a.detail for a in pages), \
                [a.detail for a in pages]

            # 3. r1 is QUARANTINED through the counted choke point —
            # excluded, not restarted
            h1 = next(h for h in fleet.replicas if h.index == 1)
            assert h1.state == QUARANTINED, h1.state
            assert h1.restart_at is None, "quarantine scheduled restart"
            assert h1.stop_reason.startswith("quarantined:"), \
                h1.stop_reason
            live = [h.index for h in fleet.replicas
                    if h.state == "ready"]
            assert live == [0, 2], live

            # 4. in-flight requests re-admitted on survivors
            assert fleet.failovers > 0, \
                "quarantine stranded no in-flight work"
            moved = [t for i, t in enumerate(fleet.completed)
                     if t.get("failovers")]
            assert moved, "no completed request records a failover"

            # 5. the audit engine's own books agree
            s = fleet.summary()["audit"]
            assert s["divergences"] >= 1, s
            assert any(q["replica"] == "r1"
                       for q in s["quarantines"]), s

        # 6. the JSONL stream renders the same story, deterministically
        events = load_events(path)
        report = build_report(events)
        assert report["requests"]["fingerprinted"] > 0, report
        assert report["divergences"], report
        assert any(d["suspect"] == "r1"
                   for d in report["divergences"]), report
        assert any(q["replica"] == 1 for q in report["quarantines"]), \
            report
        assert not is_quiet(report)
        assert to_json(report) == to_json(
            build_report(load_events(path))), "report not deterministic"
        print(render(report))

        # 7. an empty stream is a quiet rc-0 report, not a crash
        empty = os.path.join(td, "empty.jsonl")
        open(empty, "w").close()
        assert is_quiet(build_report(load_events(empty)))

    audit.reset()
    chaos.reset()
    watchtower.reset()
    print("obs_audit selftest ok: flip on r1 paged, quarantined, "
          f"{fleet.failovers} failover(s), outputs bit-identical "
          f"({len(baseline)} streams)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("jsonl", nargs="?", default="",
                    help="metrics JSONL an audited run wrote")
    ap.add_argument("--json", action="store_true",
                    help="print the canonical report JSON instead of "
                         "the table")
    ap.add_argument("--selftest", action="store_true",
                    help="run the flip->page->quarantine->re-admit "
                         "drill (tier-1 acceptance gate)")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if not args.jsonl:
        ap.error("need a metrics JSONL path (or --selftest)")
    if not os.path.exists(args.jsonl):
        print(f"no such file: {args.jsonl}")
        return 1
    report = build_report(load_events(args.jsonl))
    if is_quiet(report):
        print(f"no audit activity in {args.jsonl} "
              f"(run with TPUNN_AUDIT= armed and a metrics sink)")
        return 0
    print(to_json(report) if args.json else render(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
