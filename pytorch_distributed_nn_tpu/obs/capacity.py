"""Skyline capacity frontier: offered-load sweeps judged by the
watchtower's burn-rate signal.

The capacity question — "how many replicas does this SLO need under a
flash crowd?" — answered by measurement, not folklore: sweep offered
load (``rps_scale`` rungs of one seeded :mod:`serve.traffic` trace)
against a fleet, judge every rung with the watchtower's existing
multi-window TTFT / per-token burn-rate machinery (the rung's request
stream is replayed through a fresh :class:`obs.watchtower.Watchtower`
in event time — no new transport, no new detectors), and emit the
**capacity frontier**: the max sustainable request rate per SLO class
per traffic shape per replica count, plus the goodput-saturation knee
where marginal tokens/s per offered req/s collapses.

Two ways to produce a rung's request stream:

- :func:`simulate_fleet` — a deterministic discrete-event service
  model (per-replica decode slots, FIFO queueing, admission shedding,
  chaos ``kill_replica@`` faults with re-admission penalties). Pure in
  the trace: same spec + seed → byte-identical events → **identical
  capacity report**, with no accelerator in the loop. This is what
  tier-1 exercises (tests/test_skyline.py), and what the planning
  report defaults to.
- a real :class:`serve.fleet.Fleet` driven by
  :func:`serve.traffic.replay_trace`, whose completion records feed
  the same judge, and whose service-time parameters calibrate the
  simulator.

Chaos composes: the simulator accepts a ``TPUNN_CHAOS``-grammar spec
(parsed by :func:`runtime.chaos.parse_spec` — the real grammar, not a
clone) so a replica kill lands mid-flash-crowd; the report names the
failover window it carved out of the frontier.
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import logging
from typing import Callable, Optional, Sequence

from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.obs.stats import median
from pytorch_distributed_nn_tpu.obs.watchtower import (
    PAGE,
    WatchConfig,
    Watchtower,
)
from pytorch_distributed_nn_tpu.serve import traffic

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class SloClass:
    """One SLO class to judge every rung against."""

    name: str
    ttft_s: float
    token_s: float
    objective: float = 0.9

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


DEFAULT_SLOS = (
    SloClass("interactive", ttft_s=0.5, token_s=0.1, objective=0.9),
    SloClass("batch", ttft_s=2.0, token_s=0.5, objective=0.95),
)


def _skyline_gauges():
    reg = get_registry()
    return {
        "offered": reg.gauge(
            "skyline_offered_rps", "offered request rate at the last "
            "judged rung", labels=("shape", "replicas")),
        "goodput": reg.gauge(
            "skyline_goodput_tps", "generated tokens/s at the last "
            "judged rung", labels=("shape", "replicas")),
        "attain": reg.gauge(
            "skyline_slo_attainment", "in-SLO fraction at the last "
            "judged rung", labels=("shape", "replicas", "slo")),
        "frontier": reg.gauge(
            "skyline_sustainable_rps", "capacity frontier: max offered "
            "req/s the SLO survives", labels=("shape", "replicas",
                                              "slo")),
    }


# ---------------------------------------------------------------------------
# Deterministic service model
# ---------------------------------------------------------------------------


def _chaos_kills(chaos_spec: Optional[str]) -> list[tuple[float, int, int]]:
    """``kill_replica@`` faults from a real TPUNN_CHAOS-grammar spec →
    ``(after_s, arrival_index_gate, replica)`` kill points. ``after_s``
    is virtual trace time; a fault with only ``step=`` fires when that
    many requests have arrived (the simulator has no replica rounds)."""
    if not chaos_spec:
        return []
    from pytorch_distributed_nn_tpu.runtime import chaos

    kills = []
    for fault in chaos.parse_spec(chaos_spec):
        if fault.kind != "kill_replica":
            log.info("capacity simulator ignores chaos fault %s "
                     "(only kill_replica is modeled)", fault.spec)
            continue
        kills.append((float(fault.after_s or 0.0),
                      int(fault.step or 0), int(fault.replica)))
    return kills


def simulate_fleet(trace: list[dict], *, replicas: int, slots: int = 4,
                   prefill_tps: float = 2000.0,
                   decode_tps: float = 200.0, max_wait_s: float = 2.0,
                   readmit_s: float = 0.05,
                   chaos_spec: Optional[str] = None,
                   duration_s: Optional[float] = None) -> dict:
    """Discrete-event model of the fleet serving a trace, entirely in
    virtual time. Each replica owns ``slots`` concurrent decode slots;
    a request occupies one for ``prompt_len/prefill_tps +
    max_new/decode_tps`` seconds, TTFT = queue wait + prefill. An
    arrival that would wait longer than ``max_wait_s`` is shed
    (``queue_full``) — the admission-control analogue. A chaos kill
    removes the replica and re-admits its unfinished requests on
    survivors after ``readmit_s``, TTFT still charged from the
    *original* arrival (what the client experienced).

    Returns ``{"events", "goodput_tps", "offered_rps", "requests",
    "rejects", "failover_windows"}`` — events are watchtower-shaped
    (``serve_request`` / ``serve_reject`` / ``replica_down`` /
    ``serve_round``), sorted by event time, pure in the inputs."""
    if replicas < 1:
        raise ValueError("simulate_fleet needs replicas >= 1")
    kills = _chaos_kills(chaos_spec)
    alive = set(range(replicas))
    slot_ends = {r: [0.0] * slots for r in alive}
    # per-replica ledger of assigned-but-maybe-unfinished requests
    assigned: dict[int, list[dict]] = {r: [] for r in alive}

    # one heap of timed work: kills sort before arrivals at equal time
    _KILL, _ARRIVE = 0, 1
    heap: list[tuple[float, int, int, dict]] = []
    seq = 0
    arrivals_seen = 0
    kill_by_index = []
    for after_s, step_gate, rep in kills:
        if after_s > 0:
            heap.append((after_s, _KILL, seq, {"replica": rep}))
            seq += 1
        else:
            kill_by_index.append((step_gate, rep))
    for rec in trace:
        heap.append((float(rec["t"]), _ARRIVE, seq,
                     {"rec": rec, "t_orig": float(rec["t"]),
                      "failovers": []}))
        seq += 1
    heapq.heapify(heap)

    events: list[tuple[float, int, dict]] = []  # (t, order, event)
    eseq = 0
    completed_tokens = 0
    n_rejects = 0
    failover_windows: list[dict] = []

    def _emit(ev: dict) -> None:
        nonlocal eseq
        events.append((float(ev["t"]), eseq, ev))
        eseq += 1

    def _kill(t_kill: float, rep: int) -> None:
        nonlocal seq
        if rep not in alive:
            return
        alive.discard(rep)
        stranded = [w for w in assigned.pop(rep) if w["end"] > t_kill]
        ids = [w["id"] for w in stranded]
        _emit({"ev": "replica_down", "t": round(t_kill, 6),
               "replica": rep, "reason": "chaos_kill",
               "stranded": ids})
        for w in stranded:
            entry = dict(w["entry"])
            entry["failovers"] = entry["failovers"] + [{
                "from_replica": rep, "reason": "chaos_kill",
                "t": round(t_kill, 6), "readmit_s": readmit_s}]
            heapq.heappush(heap, (t_kill + readmit_s, _ARRIVE, seq,
                                  entry))
            seq += 1
        failover_windows.append({
            "replica": rep, "t_down": round(t_kill, 6),
            "readmitted": len(stranded), "t_recovered": None})

    while heap:
        t, kind, _, payload = heapq.heappop(heap)
        if kind == _KILL:
            _kill(t, payload["replica"])
            continue
        rec = payload["rec"]
        rid = f"t{int(rec['i']):05d}"
        arrivals_seen += 1
        while kill_by_index and kill_by_index[0][0] <= arrivals_seen:
            _, rep = kill_by_index.pop(0)
            _kill(t, rep)
        if not alive:
            n_rejects += 1
            _emit({"ev": "serve_reject", "t": round(t, 6),
                   "request_id": rid, "reason": "no_replicas"})
            continue
        # earliest-start placement, replica index breaks ties
        best_r, best_start = None, None
        for r in sorted(alive):
            start = max(t, min(slot_ends[r]))
            if best_start is None or start < best_start:
                best_r, best_start = r, start
        if best_start - t > max_wait_s:
            n_rejects += 1
            _emit({"ev": "serve_reject", "t": round(t, 6),
                   "request_id": rid, "reason": "queue_full"})
            continue
        prefill_s = float(rec["prompt_len"]) / prefill_tps
        decode_s = float(rec["max_new"]) / decode_tps
        end = best_start + prefill_s + decode_s
        ttft = (best_start - payload["t_orig"]) + prefill_s
        ends = slot_ends[best_r]
        ends[ends.index(min(ends))] = end
        work = {"id": rid, "end": end, "entry": payload}
        assigned[best_r].append(work)
        per_token = decode_s / max(int(rec["max_new"]), 1)
        ev = {"ev": "serve_request", "t": round(end, 6), "ok": True,
              "request_id": rid, "ttft_s": round(ttft, 6),
              "per_token_s": round(per_token, 6),
              "tenant": rec.get("tenant", "default"),
              "new_tokens": int(rec["max_new"]),
              "replica": f"r{best_r}",
              "failovers": payload["failovers"]}
        work["event"] = ev

    # finalize: only requests still on a live replica's ledger
    # completed (a kill popped its ledger and re-admitted the rest)
    done = [w for per in assigned.values() for w in per]
    for w in done:
        _emit(w["event"])
    completed_tokens = sum(int(w["entry"]["rec"]["max_new"])
                           for w in done)
    for win in failover_windows:
        ends = [w["end"] for w in done if w["entry"]["failovers"]
                and any(f["from_replica"] == win["replica"]
                        for f in w["entry"]["failovers"])]
        win["t_recovered"] = round(max(ends), 6) if ends else None
    # a per-token latency sample per completion, through the same
    # serve_round path the live engine feeds (wall per decoded token)
    for i, w in enumerate(sorted(done, key=lambda w: w["end"])):
        _emit({"ev": "serve_round", "t": round(w["end"], 6),
               "round": i, "wall_s": w["event"]["per_token_s"]})

    events.sort(key=lambda e: (e[0], e[1]))
    window = duration_s or 0.0
    if events:
        window = max(window, events[-1][0])
    offered = len(trace) / window if window > 0 else 0.0
    return {
        "events": [e for _, _, e in events],
        "goodput_tps": round(completed_tokens / window, 4)
        if window > 0 else 0.0,
        "offered_rps": round(offered, 4),
        "requests": len(trace),
        "rejects": n_rejects,
        "failover_windows": failover_windows,
    }


def simulate_autoscaled_fleet(
        trace: list[dict], *, controller, replicas: int,
        slots: int = 4, prefill_tps: float = 2000.0,
        decode_tps: float = 200.0, max_wait_s: float = 2.0,
        readmit_s: float = 0.05, warmup_s: float = 0.25,
        tick_s: float = 0.5, chaos_spec: Optional[str] = None,
        duration_s: Optional[float] = None,
        tail_s: float = 10.0) -> dict:
    """:func:`simulate_fleet` with the replica set under closed-loop
    control — the no-backend validation path for Helm
    (:mod:`serve.autoscale`, tests/test_autoscale.py).

    ``controller`` is duck-typed (so this module never imports the
    autoscaler; serve code reaches obs, not the reverse):
    ``feed(event)`` receives every completion's ``serve_request`` /
    ``serve_round`` event *causally* (flushed in event-time order
    before anything later happens), and
    ``desired(t, ready, queue_frac=..., kv_free_frac=...)`` is called
    once per ``tick_s`` of virtual time and returns the new replica
    target — or None to hold. Pressure evidence is the service model's
    own: ``queue_frac`` is the best-case placement wait as a fraction
    of the shed line ``max_wait_s``, ``kv_free_frac`` the fraction of
    placeable decode slots free at the tick.

    Control actions mirror the live fleet's semantics: a scale-up adds
    fresh replicas (monotonic indexes) that only become placeable
    ``warmup_s`` later (the join gate); a scale-down retires the
    highest-index replicas — immediately unplaceable, but their
    in-flight work still completes, so scaling down rejects nothing.
    Chaos ``kill_replica@`` kills compose exactly as in
    :func:`simulate_fleet`; the controller sees the resulting burn and
    is expected to buy the capacity back. Ticks continue ``tail_s``
    past the horizon so post-spike scale-downs land inside the run.

    Pure in the inputs (given a deterministic controller): returns the
    :func:`simulate_fleet` report plus ``replica_series`` (per tick:
    ``t`` / ``ready`` / ``target``), ``scale_events``, and
    ``final_target``."""
    if replicas < 1:
        raise ValueError("simulate_autoscaled_fleet needs replicas >= 1")
    kills = _chaos_kills(chaos_spec)
    members: dict[int, dict] = {}
    slot_ends: dict[int, list[float]] = {}
    assigned: dict[int, list[dict]] = {}
    next_index = 0

    def _add_replica(warm_at: float) -> int:
        nonlocal next_index
        r = next_index
        next_index += 1
        members[r] = {"warm_at": warm_at, "retiring": False,
                      "killed": False}
        slot_ends[r] = [0.0] * slots
        assigned[r] = []
        return r

    for _ in range(replicas):
        _add_replica(0.0)

    def _placeable(t: float) -> list[int]:
        return sorted(
            r for r, m in members.items()
            if not m["killed"] and not m["retiring"]
            and m["warm_at"] <= t)

    # one heap of timed work; at equal times kills land first, then
    # control ticks, then arrivals (a decision never sees the future)
    _KILL, _TICK, _ARRIVE = 0, 1, 2
    heap: list[tuple[float, int, int, dict]] = []
    seq = 0
    arrivals_seen = 0
    kill_by_index = []
    for after_s, step_gate, rep in kills:
        if after_s > 0:
            heap.append((after_s, _KILL, seq, {"replica": rep}))
            seq += 1
        else:
            kill_by_index.append((step_gate, rep))
    horizon = duration_s if duration_s is not None else (
        max((float(rec["t"]) for rec in trace), default=0.0))
    n_ticks = int((horizon + tail_s) / tick_s) + 1
    for i in range(n_ticks):
        heap.append((i * tick_s, _TICK, seq, {}))
        seq += 1
    for rec in trace:
        heap.append((float(rec["t"]), _ARRIVE, seq,
                     {"rec": rec, "t_orig": float(rec["t"]),
                      "failovers": []}))
        seq += 1
    heapq.heapify(heap)

    events: list[tuple[float, int, dict]] = []
    eseq = 0
    rounds = 0
    completed_tokens = 0
    n_rejects = 0
    failover_windows: list[dict] = []
    replica_series: list[dict] = []
    scale_events: list[dict] = []
    target = replicas
    # completion queue: works flush (emit + controller.feed) in end-
    # time order before any later pop — the controller is causal
    pending: list[tuple[float, int, dict]] = []
    pseq = 0

    def _emit(ev: dict) -> None:
        nonlocal eseq
        events.append((float(ev["t"]), eseq, ev))
        eseq += 1

    def _flush(t: float) -> None:
        nonlocal rounds, completed_tokens
        while pending and pending[0][0] <= t + 1e-12:
            _, _, w = heapq.heappop(pending)
            if w.get("stranded"):
                continue  # re-admitted by a kill; a later life flushes
            w["flushed"] = True
            _emit(w["event"])
            controller.feed(w["event"])
            rev = {"ev": "serve_round", "t": w["event"]["t"],
                   "round": rounds,
                   "wall_s": w["event"]["per_token_s"]}
            rounds += 1
            _emit(rev)
            controller.feed(rev)
            completed_tokens += int(w["entry"]["rec"]["max_new"])

    def _kill(t_kill: float, rep: int) -> None:
        nonlocal seq
        m = members.get(rep)
        if m is None or m["killed"]:
            return
        m["killed"] = True
        stranded = [w for w in assigned[rep]
                    if not w.get("flushed") and w["end"] > t_kill]
        ids = [w["id"] for w in stranded]
        ev = {"ev": "replica_down", "t": round(t_kill, 6),
              "replica": rep, "reason": "chaos_kill", "stranded": ids}
        _emit(ev)
        controller.feed(ev)
        for w in stranded:
            w["stranded"] = True
            entry = dict(w["entry"])
            entry["failovers"] = entry["failovers"] + [{
                "from_replica": rep, "reason": "chaos_kill",
                "t": round(t_kill, 6), "readmit_s": readmit_s}]
            heapq.heappush(heap, (t_kill + readmit_s, _ARRIVE, seq,
                                  entry))
            seq += 1
        failover_windows.append({
            "replica": rep, "t_down": round(t_kill, 6),
            "readmitted": len(stranded), "t_recovered": None})

    while heap:
        t, kind, _, payload = heapq.heappop(heap)
        _flush(t)
        if kind == _KILL:
            _kill(t, payload["replica"])
            continue
        if kind == _TICK:
            cands = _placeable(t)
            ready = len(cands)
            if cands:
                waits = [max(0.0, min(slot_ends[r]) - t)
                         for r in cands]
                queue_frac = (min(1.0, min(waits) / max_wait_s)
                              if max_wait_s > 0 else 0.0)
                free = sum(1 for r in cands
                           for e in slot_ends[r] if e <= t)
                kv_free_frac = free / (len(cands) * slots)
            else:
                queue_frac, kv_free_frac = 1.0, 0.0
            n = controller.desired(
                round(t, 6), ready,
                queue_frac=round(queue_frac, 6),
                kv_free_frac=round(kv_free_frac, 6))
            cur = sum(1 for m in members.values()
                      if not m["killed"] and not m["retiring"])
            if n is not None and n != cur:
                if n > cur:
                    for _ in range(n - cur):
                        r = _add_replica(round(t + warmup_s, 6))
                        scale_events.append(
                            {"t": round(t, 6), "op": "add",
                             "replica": r,
                             "warm_at": members[r]["warm_at"]})
                else:
                    live = sorted(
                        (r for r, m in members.items()
                         if not m["killed"] and not m["retiring"]),
                        reverse=True)
                    for r in live[:cur - n]:
                        members[r]["retiring"] = True
                        scale_events.append(
                            {"t": round(t, 6), "op": "retire",
                             "replica": r})
            if n is not None:
                target = n
            replica_series.append({"t": round(t, 6), "ready": ready,
                                   "target": target})
            continue
        rec = payload["rec"]
        rid = f"t{int(rec['i']):05d}"
        arrivals_seen += 1
        while kill_by_index and kill_by_index[0][0] <= arrivals_seen:
            _, rep = kill_by_index.pop(0)
            _kill(t, rep)
        cands = _placeable(t)
        if not cands:
            n_rejects += 1
            rej = {"ev": "serve_reject", "t": round(t, 6),
                   "request_id": rid, "reason": "no_replicas"}
            _emit(rej)
            controller.feed(rej)
            continue
        best_r, best_start = None, None
        for r in cands:
            start = max(t, min(slot_ends[r]))
            if best_start is None or start < best_start:
                best_r, best_start = r, start
        if best_start - t > max_wait_s:
            n_rejects += 1
            rej = {"ev": "serve_reject", "t": round(t, 6),
                   "request_id": rid, "reason": "queue_full"}
            _emit(rej)
            controller.feed(rej)
            continue
        prefill_s = float(rec["prompt_len"]) / prefill_tps
        decode_s = float(rec["max_new"]) / decode_tps
        end = best_start + prefill_s + decode_s
        ttft = (best_start - payload["t_orig"]) + prefill_s
        ends = slot_ends[best_r]
        ends[ends.index(min(ends))] = end
        per_token = decode_s / max(int(rec["max_new"]), 1)
        work = {"id": rid, "end": end, "entry": payload,
                "event": {"ev": "serve_request", "t": round(end, 6),
                          "ok": True, "request_id": rid,
                          "ttft_s": round(ttft, 6),
                          "per_token_s": round(per_token, 6),
                          "tenant": rec.get("tenant", "default"),
                          "new_tokens": int(rec["max_new"]),
                          "replica": f"r{best_r}",
                          "failovers": payload["failovers"]}}
        assigned[best_r].append(work)
        heapq.heappush(pending, (end, pseq, work))
        pseq += 1

    _flush(float("inf"))
    for win in failover_windows:
        ends = [w["end"] for per in assigned.values() for w in per
                if w.get("flushed") and w["entry"]["failovers"]
                and any(f["from_replica"] == win["replica"]
                        for f in w["entry"]["failovers"])]
        win["t_recovered"] = round(max(ends), 6) if ends else None

    events.sort(key=lambda e: (e[0], e[1]))
    window = duration_s or 0.0
    if events:
        window = max(window, events[-1][0])
    offered = len(trace) / window if window > 0 else 0.0
    return {
        "events": [e for _, _, e in events],
        "goodput_tps": round(completed_tokens / window, 4)
        if window > 0 else 0.0,
        "offered_rps": round(offered, 4),
        "requests": len(trace),
        "rejects": n_rejects,
        "failover_windows": failover_windows,
        "replica_series": replica_series,
        "scale_events": scale_events,
        "final_target": target,
    }


# ---------------------------------------------------------------------------
# The judge: watchtower burn over a rung's event stream
# ---------------------------------------------------------------------------


def judge_rung(events: Sequence[dict], *, slo: SloClass,
               duration_s: float) -> dict:
    """Replay a rung's request stream through a fresh
    :class:`Watchtower` (event time only) configured for this SLO
    class, windows scaled to the rung. Sustainable = the burn-rate
    detector never paged AND the raw in-SLO fraction meets the
    objective — the same multi-window signal production paging uses,
    so the frontier and the pager can never disagree."""
    window = max(float(duration_s), 1e-3)
    cfg = WatchConfig(
        ttft_slo_s=slo.ttft_s, token_slo_s=slo.token_s,
        slo_objective=slo.objective,
        burn_fast_s=max(window / 4.0, 1e-3), burn_slow_s=window,
        burn_threshold=2.0, burn_min_events=5)
    tower = Watchtower(cfg, dump_on_page=False)
    total = 0
    in_slo = 0
    for ev in events:
        kind = ev.get("ev")
        if kind == "serve_request":
            total += 1
            if ev.get("ok", True) and float(ev["ttft_s"]) <= slo.ttft_s:
                in_slo += 1
        elif kind == "serve_reject":
            total += 1
        tower.observe(ev)
    attainment = in_slo / total if total else 1.0
    burn_pages = [a for a in tower.alerts
                  if a.kind == "slo_burn_rate" and a.severity == PAGE]
    return {
        "slo": slo.name,
        "attainment": round(attainment, 4),
        "objective": slo.objective,
        "burn_pages": len(burn_pages),
        "burned_slos": sorted({a.attribution.get("slo", "?")
                               for a in burn_pages}),
        "sustainable": (not burn_pages
                        and attainment >= slo.objective),
    }


# ---------------------------------------------------------------------------
# Sweep + frontier + knee
# ---------------------------------------------------------------------------


def sweep_rates(spec: traffic.TrafficSpec, *,
                rates: Sequence[float], run_rung: Callable[..., dict],
                slos: Sequence[SloClass] = DEFAULT_SLOS,
                seed: int = 0) -> list[dict]:
    """One replica count's sweep: for each ``rps_scale`` rung,
    regenerate the trace at that offered load (same seed — the rungs
    are the *same* traffic shape, scaled) and judge it against every
    SLO class. ``run_rung(trace, duration_s)`` produces the rung's
    event stream (simulator or a live fleet driver)."""
    rungs = []
    for scale in rates:
        trace = traffic.generate_trace(spec, seed=seed,
                                       rps_scale=scale)
        run = run_rung(trace, spec.duration_s)
        rung = {
            "rate_scale": scale,
            "offered_rps": run["offered_rps"],
            "requests": run["requests"],
            "rejects": run["rejects"],
            "goodput_tps": run["goodput_tps"],
            "failover_windows": run.get("failover_windows", []),
            "slo": {s.name: judge_rung(run["events"], slo=s,
                                       duration_s=spec.duration_s)
                    for s in slos},
        }
        rungs.append(rung)
        log.info("capacity rung x%.2f: offered %.2f rps, goodput "
                 "%.1f tok/s, sustainable=%s", scale,
                 rung["offered_rps"], rung["goodput_tps"],
                 {k: v["sustainable"] for k, v in rung["slo"].items()})
    return rungs


def frontier_of(rungs: Sequence[dict],
                slos: Sequence[SloClass] = DEFAULT_SLOS) -> dict:
    """Max sustainable offered rate per SLO class (None when even the
    lowest rung burned)."""
    out = {}
    for s in slos:
        ok = [r["offered_rps"] for r in rungs
              if r["slo"][s.name]["sustainable"]]
        out[s.name] = max(ok) if ok else None
    return out


def knee_of(rungs: Sequence[dict]) -> Optional[float]:
    """The goodput-saturation knee: the offered rate where marginal
    goodput per offered req/s first drops under half the reference
    slope (median of the early slopes — heavy-tail-robust, the
    obs.stats helpers). None when the sweep never saturates."""
    pts = sorted((r["offered_rps"], r["goodput_tps"]) for r in rungs)
    slopes = []
    for (x0, y0), (x1, y1) in zip(pts, pts[1:]):
        if x1 > x0:
            slopes.append(((y1 - y0) / (x1 - x0), x1))
    if len(slopes) < 2:
        return None
    head = [s for s, _ in slopes[:max(1, len(slopes) // 2)]]
    ref = median(head)
    if ref <= 0:
        return None
    for slope, x in slopes:
        if slope < 0.5 * ref:
            return x
    return None


def plan_capacity(spec: traffic.TrafficSpec, *,
                  replica_counts: Sequence[int],
                  rates: Sequence[float],
                  make_run_rung: Callable[[int], Callable[..., dict]],
                  slos: Sequence[SloClass] = DEFAULT_SLOS,
                  seed: int = 0, target_rps: Optional[float] = None,
                  chaos_spec: Optional[str] = None,
                  price_per_replica_hour: float = 0.0) -> dict:
    """The full capacity-planning sweep: replica counts x offered-load
    rungs x SLO classes → the frontier surface and the headline table
    "replicas needed per SLO per traffic shape" (min replica count
    whose frontier covers ``target_rps``, default the spec's base
    rate). Pure in (spec, seed, service model): generating the report
    twice yields identical JSON — the determinism contract tier-1
    asserts.

    ``price_per_replica_hour`` > 0 prices every rung (the Abacus
    showback bridge, obs/meter.py): ``cost_per_1k_tokens = replicas x
    price / 3600 x 1000 / goodput_tps`` — the planner's answer to
    "which replica count serves this shape CHEAPEST per token while
    holding the SLO", not just "which is smallest". Keys are absent at
    the default 0.0 so unpriced reports stay byte-identical."""
    target = float(target_rps if target_rps is not None
                   else spec.base_rps)
    gauges = _skyline_gauges()
    shape = spec.shape_name
    sweeps = {}
    for n in replica_counts:
        rungs = sweep_rates(spec, rates=rates,
                            run_rung=make_run_rung(n), slos=slos,
                            seed=seed)
        if price_per_replica_hour > 0:
            for rung in rungs:
                tps = rung["goodput_tps"]
                rung["cost_per_1k_tokens"] = (
                    round(n * price_per_replica_hour / 3600.0
                          * 1000.0 / tps, 6) if tps > 0 else None)
        front = frontier_of(rungs, slos)
        sweeps[str(n)] = {"rungs": rungs, "frontier": front,
                          "knee_rps": knee_of(rungs)}
        last = rungs[-1]
        gauges["offered"].set(last["offered_rps"], shape=shape,
                              replicas=str(n))
        gauges["goodput"].set(last["goodput_tps"], shape=shape,
                              replicas=str(n))
        for s in slos:
            gauges["attain"].set(last["slo"][s.name]["attainment"],
                                 shape=shape, replicas=str(n),
                                 slo=s.name)
            gauges["frontier"].set(front[s.name] or 0.0, shape=shape,
                                   replicas=str(n), slo=s.name)
    needed = {}
    for s in slos:
        counts = [n for n in sorted(replica_counts)
                  if (sweeps[str(n)]["frontier"][s.name] or 0.0)
                  >= target]
        needed[s.name] = {"target_rps": round(target, 4),
                          "replicas": min(counts) if counts else None}
    report = {
        "shape": shape,
        "spec": spec.describe(),
        "seed": seed,
        "chaos": chaos_spec or "",
        "slos": [s.as_dict() for s in slos],
        "replica_counts": sorted(int(n) for n in replica_counts),
        "sweeps": sweeps,
        "replicas_needed": needed,
    }
    if price_per_replica_hour > 0:
        report["price_per_replica_hour"] = round(
            float(price_per_replica_hour), 6)
    return report


def simulated_run_rung(replicas: int, *, slots: int = 4,
                       prefill_tps: float = 2000.0,
                       decode_tps: float = 200.0,
                       max_wait_s: float = 2.0,
                       readmit_s: float = 0.05,
                       chaos_spec: Optional[str] = None
                       ) -> Callable[..., dict]:
    """``make_run_rung`` for :func:`plan_capacity` backed by the
    deterministic service model."""
    def run(trace: list[dict], duration_s: float) -> dict:
        return simulate_fleet(
            trace, replicas=replicas, slots=slots,
            prefill_tps=prefill_tps, decode_tps=decode_tps,
            max_wait_s=max_wait_s, readmit_s=readmit_s,
            chaos_spec=chaos_spec, duration_s=duration_s)
    return run


# ---------------------------------------------------------------------------
# Serialization (byte-identical report contract) + JSONL events
# ---------------------------------------------------------------------------


def report_to_json(report: dict) -> str:
    """Canonical serialization — same spec + seed + service model →
    the same bytes twice in a row."""
    return json.dumps(report, sort_keys=True)


def report_events(report: dict) -> list[dict]:
    """Flatten a capacity report into JSONL-able events
    (``capacity_rung`` / ``capacity_frontier``) for the metrics stream
    ``scripts/obs_report.py --capacity`` renders."""
    out = []
    for n, sweep in sorted(report["sweeps"].items(),
                           key=lambda kv: int(kv[0])):
        for rung in sweep["rungs"]:
            out.append({
                "event": "capacity_rung", "shape": report["shape"],
                "replicas": int(n),
                "offered_rps": rung["offered_rps"],
                "goodput_tps": rung["goodput_tps"],
                "rejects": rung["rejects"],
                "requests": rung["requests"],
                "slo": {name: {"attainment": j["attainment"],
                               "sustainable": j["sustainable"],
                               "burn_pages": j["burn_pages"]}
                        for name, j in rung["slo"].items()},
                "failover_windows": rung["failover_windows"],
            })
        out.append({
            "event": "capacity_frontier", "shape": report["shape"],
            "replicas": int(n), "frontier": sweep["frontier"],
            "knee_rps": sweep["knee_rps"], "chaos": report["chaos"],
        })
    out.append({
        "event": "capacity_plan", "shape": report["shape"],
        "spec": report["spec"], "seed": report["seed"],
        "chaos": report["chaos"],
        "replicas_needed": report["replicas_needed"],
    })
    return out
