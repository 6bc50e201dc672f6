#!/usr/bin/env python
"""Render Causeway request traces: waterfalls, critical paths, rollups.

Input is any of the places spans land (obs/trace.py emits them):

- a JSON file holding a span list, or a (merged) Chrome trace whose
  ``cat == "trace"`` events carry spans in ``args`` (the
  ``obs.trace.spans_to_chrome`` / ``obs.span.merge_chrome_traces``
  round trip);
- a metrics JSONL file — every ``event == "trace_span"`` record;
- a live store: ``--store host:port --ranks N`` pulls every published
  per-host buffer (``obs.aggregate.collect_spans``) — the
  process-fleet path, where each ``fleet_worker`` publishes its own
  spans at ``trace/<idx>``.

Per trace: the waterfall (one bar per duration span, offset from the
trace's first instant) and the critical path — every instant of the
observed extent attributed to exactly one segment (transfer > failover
> restore > prefill > decode > queued; uncovered time is ``stitch``),
so the per-segment seconds provably sum to end-to-end latency.
``--rollup`` prints the fleet view per SLO latency band instead.

Usage:
    python scripts/obs_trace.py spans.json               # all traces
    python scripts/obs_trace.py merged.trace.json --trace a3f0
    python scripts/obs_trace.py run.jsonl --rollup
    python scripts/obs_trace.py --store 127.0.0.1:29500 --ranks 4
    python scripts/obs_trace.py --selftest               # tier-1 gate

``--selftest`` is the deterministic no-accelerator acceptance drill
(tier-1 via tests/test_quality.py): one request through a
disaggregated fleet with a ``kill_transfer@`` chaos kill mid-stream
must yield ONE merged trace whose queued/prefill/transfer/failover/
decode segments sum to the measured end-to-end latency within 1%,
with the re-admitted decode leg linked to the original trace — and the
whole drill must produce byte-identical canonical trace JSON when run
twice with the same seed.
"""

from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.obs import critpath  # noqa: E402
from pytorch_distributed_nn_tpu.obs import trace as tracemod  # noqa: E402

BAR_W = 40


def load_spans(path: str) -> list[dict]:
    """Span dicts from a span-list JSON, a Chrome trace, or a metrics
    JSONL stream (``kind == "trace_span"`` events)."""
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if head in ("[", "{"):
            try:
                doc = json.load(f)
            except json.JSONDecodeError:
                doc = None
            if isinstance(doc, dict):
                return critpath.spans_from_chrome(
                    doc.get("traceEvents", []))
            if isinstance(doc, list):
                if doc and doc[0].get("ph"):
                    return critpath.spans_from_chrome(doc)
                return doc
            f.seek(0)
        spans = []
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line — the JSONL contract
            if ev.get("event") == "trace_span":
                spans.append({k: v for k, v in ev.items()
                              if k not in ("event", "time", "process")})
        return spans


def pull_spans(endpoint: str, ranks: int, namespace: str) -> list[dict]:
    from pytorch_distributed_nn_tpu.obs import aggregate
    from pytorch_distributed_nn_tpu.serve.store import (
        PrefixStore,
        make_store,
    )

    client = make_store(endpoint)
    ps = PrefixStore(client, namespace) if namespace else client
    try:
        return aggregate.collect_spans(ps, range(ranks))
    finally:
        try:
            client.close()
        except OSError:
            pass


def print_waterfall(spans: list[dict], trace_id: str) -> None:
    wf = critpath.waterfall(spans, trace_id)
    cp = wf["critical_path"]
    total = cp["total_s"]
    legs = ", ".join(
        f"leg{n}@{'+'.join(leg['hosts'])}"
        for n, leg in wf["legs"].items())
    print(f"== trace {trace_id} ==  {total * 1e3:.1f}ms end-to-end, "
          f"{len(wf['rows'])} span(s), {legs} "
          f"(linked={'yes' if wf['linked'] else 'NO'})")
    for row in wf["rows"]:
        if total > 0:
            lo = int(BAR_W * row["start_s"] / total)
            hi = int(BAR_W * (row["start_s"] + row["dur_s"]) / total)
            bar = " " * lo + "#" * max(hi - lo, 1)
        else:
            bar = "#"
        extra = " ".join(f"{k}={v}" for k, v in
                         sorted(row["attrs"].items())
                         if k not in ("request_id",))
        print(f"  leg{row['leg']} {row['segment']:>9} "
              f"|{bar:<{BAR_W}}| {row['dur_s'] * 1e3:8.1f}ms  {extra}")
    parts = "  ".join(
        f"{seg}={sec * 1e3:.1f}ms"
        for seg, sec in sorted(cp["segments"].items(),
                               key=lambda kv: -kv[1]))
    print(f"  critical path: {parts}  (dominant: {cp['dominant']})")


def print_rollup(spans: list[dict]) -> None:
    roll = critpath.rollup(spans)
    if not roll:
        print("no traces")
        return
    print(f"{'band':>8} {'traces':>7} {'dominant':>10}  per-segment "
          f"p50/p99 (ms)")
    for band, row in roll.items():
        segs = "  ".join(
            f"{seg}={st['p50_s'] * 1e3:.1f}/{st['p99_s'] * 1e3:.1f}"
            for seg, st in row["segments"].items())
        print(f"{band:>8} {row['traces']:>7} {row['dominant']:>10}  "
              f"{segs}")


def _render(spans: list[dict], args) -> int:
    if not spans:
        # a quiet report, not a failure: the stream simply ran with
        # Causeway unarmed (TPUNN_TRACE unset)
        print("no trace spans found")
        return 0
    trace_ids = sorted({str(s.get("trace", "")) for s in spans})
    if args.trace:
        trace_ids = [t for t in trace_ids
                     if t.startswith(args.trace)]
        if not trace_ids:
            # an explicit trace-id filter that matches nothing IS an
            # operator error — keep that loud
            print(f"no trace matching {args.trace!r}")
            return 1
    if args.json:
        if args.rollup:
            print(json.dumps(critpath.rollup(spans), indent=2))
        else:
            print(json.dumps(
                {t: critpath.waterfall(spans, t) for t in trace_ids},
                indent=2))
        return 0
    if args.rollup:
        print_rollup(spans)
        return 0
    for t in trace_ids:
        print_waterfall(spans, t)
        print()
    return 0


# ---------------------------------------------------------------------------
# --selftest: the deterministic disagg kill_transfer drill (tier-1)
# ---------------------------------------------------------------------------


def _drill() -> tuple[list[dict], float]:
    """One traced request through a disaggregated fleet with the first
    KV transfer killed mid-stream. Returns (spans, measured e2e
    seconds). The tiny 2-layer llama is tests/test_disagg.py's:
    CPU-scale, seed-pinned, greedy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.obs import flight
    from pytorch_distributed_nn_tpu.runtime import chaos
    from pytorch_distributed_nn_tpu.serve import Fleet
    from pytorch_distributed_nn_tpu.serve.disagg import DisaggFleet

    vocab = 97
    model = get_model(ModelConfig(
        name="llama3_8b", compute_dtype="float32", dtype="float32",
        extra=dict(num_layers=2, d_model=64, num_heads=4,
                   num_kv_heads=2, mlp_dim=128, vocab_size=vocab)))
    params = model.init(jax.random.key(1),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    rng = np.random.default_rng(7)
    # 34 tokens = 2 full 16-token blocks: the prefill leg's chain is
    # streamable, so the decode-leg placement warm-pulls through
    # kv_transfer — where the chaos kill fires
    prompt = rng.integers(1, vocab, size=(34,)).astype(np.int32)

    tracemod.reset()
    chaos.reset()
    flight.reset_recorder(enabled=True)
    tracemod.maybe_init("1", rank=0)
    chaos.maybe_init("kill_transfer@step=1", rank=0, seed=0)
    fleet = Fleet(model, params, prefill=2, decode=2, max_slots=2,
                  max_seq_len=64, block_size=16)
    assert isinstance(fleet, DisaggFleet), type(fleet)
    ticket = fleet.submit(prompt, 6, request_id="trace-selftest-0")
    fleet.run_until_idle()
    assert ticket.ok, (ticket.status, ticket.reject_reason)
    e2e_s = ticket.t_done - ticket.t_submit
    assert any(t["outcome"] == "failed" for t in fleet.transfers), \
        f"chaos kill never hit the transfer: {fleet.transfers}"
    spans = tracemod.export_spans()
    tracemod.reset()
    chaos.reset()
    return spans, e2e_s


def _selftest() -> int:
    import tempfile

    from pytorch_distributed_nn_tpu.obs.span import merge_chrome_traces

    spans, e2e_s = _drill()
    assert spans, "armed drill emitted no spans"

    # cross-host merge path: split the spans across two chrome files
    # (as two worker hosts would write them), merge, read back — the
    # round trip must be lossless
    ids = sorted({s["trace"] for s in spans})
    assert len(ids) == 1, f"expected ONE merged trace, got {ids}"
    trace_id = ids[0]
    half = [s for s in spans if s["leg"] == 0]
    rest = [s for s in spans if s["leg"] != 0]
    assert half and rest, "drill never produced a second leg"
    with tempfile.TemporaryDirectory(prefix="tpunn-trace-") as d:
        paths = []
        for i, part in enumerate((half, rest)):
            p = f"{d}/host{i}.trace.json"
            with open(p, "w") as f:
                json.dump({"traceEvents":
                           tracemod.spans_to_chrome(part, pid=i)}, f)
            paths.append(p)
        merged = merge_chrome_traces(paths, f"{d}/merged.trace.json")
        with open(merged) as f:
            back = critpath.spans_from_chrome(
                json.load(f)["traceEvents"])
    assert len(back) == len(spans), (len(back), len(spans))

    wf = critpath.waterfall(back, trace_id)
    cp = wf["critical_path"]
    assert wf["linked"], \
        f"re-admitted leg not linked to the original trace: {wf['legs']}"
    for seg in ("queued", "prefill", "transfer", "failover", "decode"):
        assert seg in cp["segments"], \
            f"missing {seg} in critical path: {sorted(cp['segments'])}"
    total = sum(cp["segments"].values())
    assert abs(total - cp["total_s"]) < 1e-9, \
        "critical path is not a partition"
    err = abs(cp["total_s"] - e2e_s) / max(e2e_s, 1e-9)
    assert err <= 0.01, \
        (f"segments sum {cp['total_s']:.6f}s vs measured e2e "
         f"{e2e_s:.6f}s ({err:.2%} off, budget 1%)")

    # determinism gate: the same seeded drill twice must yield
    # byte-identical canonical (structure-only) trace JSON
    spans2, _ = _drill()
    a = critpath.canonical_json(spans)
    b = critpath.canonical_json(spans2)
    assert a == b, "same seed produced different canonical trace JSON"

    print_waterfall(back, trace_id)
    print(f"e2e {e2e_s * 1e3:.1f}ms vs attributed "
          f"{cp['total_s'] * 1e3:.1f}ms ({err:.2%} off)")
    print("trace selftest ok")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render Causeway trace waterfalls / critical "
                    "paths / fleet rollups")
    ap.add_argument("path", nargs="?",
                    help="span-list JSON, Chrome trace, or metrics "
                         "JSONL file")
    ap.add_argument("--trace", default="",
                    help="render only traces whose id starts with this")
    ap.add_argument("--rollup", action="store_true",
                    help="fleet rollup per SLO latency band instead "
                         "of per-trace waterfalls")
    ap.add_argument("--store", default="",
                    help="pull published spans from a live store "
                         "(host:port)")
    ap.add_argument("--ranks", type=int, default=4,
                    help="ranks to pull with --store")
    ap.add_argument("--namespace", default="fleet",
                    help="store key namespace (--store)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--selftest", action="store_true",
                    help="deterministic disagg kill_transfer tracing "
                         "drill (no accelerator; tier-1 gate)")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if args.store:
        return _render(pull_spans(args.store, args.ranks,
                                  args.namespace), args)
    if not args.path:
        ap.error("need a file, --store, or --selftest")
    try:
        spans = load_spans(args.path)
    except OSError as e:
        print(f"cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    return _render(spans, args)


if __name__ == "__main__":
    sys.exit(main())
