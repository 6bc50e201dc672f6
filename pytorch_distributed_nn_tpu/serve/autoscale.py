"""Helm: SLO burn-rate autoscaler — the watchtower → fleet closed loop.

PR 11 (Skyline) answered "how many replicas does this traffic need?"
offline; the watchtower pages when the error budget burns. Helm closes
the loop: a control policy that grows and shrinks the
:class:`serve.fleet.Fleet` replica set from signals the stack already
emits — no new transport, no new probes:

- **scale up** when any SLO's *fast-window* burn rate
  (:meth:`obs.watchtower.Watchtower.burn_rates`, the very windows the
  pager reads) crosses ``burn_up``, or queue depth / KV headroom
  (:func:`serve.router.fleet_pressure`, the router's own gauges)
  shows sustained pressure — the goal is to act *before* the
  multi-window page would fire;
- **scale down** only on sustained multi-window headroom: every burn
  under ``burn_down`` on BOTH windows, queue near-empty, KV free —
  and never below the Skyline forecast (``plan_capacity``'s
  ``replicas_needed``), so the steady state converges to the offline
  answer instead of oscillating around it;
- **no flapping**: consecutive-evaluation streaks (``up_consecutive``
  / ``down_consecutive``), per-direction cooldowns, and min/max
  bounds. A chaos blip or a flash-crowd edge moves a streak counter,
  not the fleet.

Every decision — including every *hold* — is explainable: a
:class:`Decision` journals the full evidence snapshot (per-SLO
fast/slow burns, fleet queue/KV fractions, ready count, forecast,
pre-decision hysteresis state, the spec that parameterized the
policy) plus the action and a named reason. The journal is the
byte-identical-replay unit (``as_json()`` is canonical, event-time
only — no wall clock), so ``scripts/obs_watch.py --autoscale`` can
shadow-replay a recorded run through :func:`decide` offline and diff
what Helm *would* have done against what it did.

Design contracts (lint-enforced by tests/test_quality.py):

- **inert when unset** — every module-level ``on_*`` hook opens with a
  literal ``if _helm is None: return``; an unarmed autoscaler performs
  zero registry or flight-ring writes (the chaos/watch/xray
  precedent), and instruments register lazily on the first decision;
- **emit-first** — :meth:`Autoscaler._emit`'s first statement is the
  flight-ring record, so a post-mortem can never miss the decision
  that preceded a crash.

Env contract: ``TPUNN_AUTOSCALE=1`` arms the defaults;
``TPUNN_AUTOSCALE=max_replicas=6:burn_up=1.5`` overrides
:class:`AutoscaleConfig` fields (``:``-separated ``key=value``; a
typo'd key fails loudly, never silently scales nothing). Validation:
tests/test_autoscale.py (simulated fleet, tier-1).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Optional

from pytorch_distributed_nn_tpu.obs import flight, watchtower
from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.serve import router as _router

log = logging.getLogger(__name__)

ENV_AUTOSCALE = "TPUNN_AUTOSCALE"

SCALE_UP = "scale_up"
SCALE_DOWN = "scale_down"
HOLD = "hold"
ACTIONS = (SCALE_UP, SCALE_DOWN, HOLD)

# the pool a pool-less decision governs: the decode pool IS the
# unified pool (pre-disagg journals replay unchanged — an absent
# "pool" field means decode)
DEFAULT_POOL = "decode"


@dataclasses.dataclass
class AutoscaleConfig:
    """Control-policy knobs; every field is overridable through the
    ``TPUNN_AUTOSCALE`` spec (see :func:`parse_spec`)."""

    min_replicas: int = 1
    max_replicas: int = 8
    # pressure lines (scale-up triggers; any one of them counts)
    burn_up: float = 1.0           # fast-window burn to call pressure
    queue_up: float = 0.5          # fleet queue_depth/max_queue
    kv_up: float = 0.1             # fleet free/total KV at-or-under
    # headroom lines (scale-down gates; ALL must hold)
    burn_down: float = 0.5         # both windows at-or-under
    queue_down: float = 0.1
    kv_down: float = 0.5
    # hysteresis: consecutive evaluations before acting
    up_consecutive: int = 2
    down_consecutive: int = 5
    # step sizes and cooldowns
    up_step: int = 1
    down_step: int = 1
    cooldown_up_s: float = 5.0     # between consecutive scale-ups
    cooldown_down_s: float = 30.0  # after ANY change before shrinking
    # evaluation cadence (maybe_evaluate debounce, event time)
    eval_interval_s: float = 1.0


_FIELD_TYPES = {f.name: f.type
                for f in dataclasses.fields(AutoscaleConfig)}


def parse_spec(spec: str) -> AutoscaleConfig:
    """``TPUNN_AUTOSCALE`` spec → :class:`AutoscaleConfig`. ``"1"`` /
    ``"on"`` mean defaults; otherwise ``:``-separated ``key=value``
    overrides. Unknown keys raise (a typo'd autoscale spec must fail
    loudly, not silently hold the fleet flat — the chaos-spec
    contract)."""
    cfg = AutoscaleConfig()
    spec = (spec or "").strip()
    if spec in ("", "1", "on", "true"):
        return cfg
    for field in filter(None, spec.split(":")):
        key, eq, value = field.partition("=")
        key = key.strip()
        if not eq or key not in _FIELD_TYPES:
            raise ValueError(
                f"unknown autoscale key {key!r} in {spec!r}; have "
                f"{sorted(_FIELD_TYPES)}")
        try:
            kind = _FIELD_TYPES[key]
            setattr(cfg, key,
                    int(value) if kind in (int, "int") else float(value))
        except ValueError:
            raise ValueError(f"bad value for autoscale key {key!r}: "
                             f"{value!r}") from None
    if cfg.min_replicas < 1:
        raise ValueError(
            f"autoscale min_replicas must be >= 1, got "
            f"{cfg.min_replicas}")
    if cfg.max_replicas < cfg.min_replicas:
        raise ValueError(
            f"autoscale max_replicas ({cfg.max_replicas}) < "
            f"min_replicas ({cfg.min_replicas})")
    return cfg


@dataclasses.dataclass
class Decision:
    """One journaled control decision. ``evidence`` is the complete
    input snapshot, ``state`` the PRE-decision hysteresis state, and
    ``spec`` the policy parameterization — together they make the
    record self-contained: :func:`replay_decision` re-derives
    ``action``/``reason``/``to_replicas`` from the record alone."""

    seq: int
    t: float                # event time (trace-relative; never wall)
    action: str             # SCALE_UP | SCALE_DOWN | HOLD
    reason: str             # named cause ("burn:ttft+queue", "at_max")
    from_replicas: int      # READY count when evaluated
    to_replicas: int        # size intent after this decision
    evidence: dict
    state: dict
    spec: str
    # which coordinator life wrote the record: a recovered coordinator
    # CONTINUES the journal (seq keeps counting, state chains) rather
    # than forking it, and this field marks where the boundary fell
    coordinator_incarnation: int = 0
    # which replica pool this decision sizes: "decode" (the unified
    # pool's name — legacy journals replay unchanged) or "prefill" on
    # a disaggregated process fleet (Breakwater). Hysteresis state
    # chains per pool; seq stays contiguous across pools.
    pool: str = DEFAULT_POOL

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def as_json(self) -> str:
        """Canonical serialization — the byte-identical-replay unit."""
        return json.dumps(self.as_dict(), sort_keys=True)


def decide(cfg: AutoscaleConfig, evidence: dict, state: dict,
           t: float) -> tuple:
    """The pure policy core: ``(evidence, state, t)`` →
    ``(action, reason, to_replicas, new_state)``. No clocks, no I/O,
    no globals — live control, the Skyline simulation, and the
    ``obs_watch --autoscale`` shadow replay all run exactly this.

    ``state`` carries the hysteresis memory: ``up_streak`` /
    ``down_streak`` (consecutive pressure/headroom evaluations) and
    ``last_up_t`` / ``last_change_t`` (cooldown anchors, event
    time)."""
    target = int(evidence["target"])
    burns = evidence.get("burn", {})
    queue_frac = float(evidence.get("queue_frac", 0.0))
    kv_free = float(evidence.get("kv_free_frac", 1.0))

    pressure = []
    for slo in sorted(burns):
        if float(burns[slo]["fast"]) >= cfg.burn_up:
            pressure.append(f"burn:{slo}")
    if queue_frac >= cfg.queue_up:
        pressure.append("queue")
    if kv_free <= cfg.kv_up and int(evidence.get("ready", 0)) > 0:
        pressure.append("kv")
    headroom = (not pressure
                and all(float(b["fast"]) <= cfg.burn_down
                        and float(b["slow"]) <= cfg.burn_down
                        for b in burns.values())
                and queue_frac <= cfg.queue_down
                and kv_free >= cfg.kv_down)

    new_state = dict(state)
    new_state["up_streak"] = state.get("up_streak", 0) + 1 \
        if pressure else 0
    new_state["down_streak"] = state.get("down_streak", 0) + 1 \
        if headroom else 0

    last_up = state.get("last_up_t")
    last_change = state.get("last_change_t")
    action, reason, to = HOLD, "steady", target
    if pressure:
        if target >= cfg.max_replicas:
            reason = "at_max"
        elif new_state["up_streak"] < cfg.up_consecutive:
            reason = "pressure_building"
        elif last_up is not None and t - last_up < cfg.cooldown_up_s:
            reason = "cooldown_up"
        else:
            action = SCALE_UP
            reason = "+".join(pressure)
            to = min(target + cfg.up_step, cfg.max_replicas)
            new_state["last_up_t"] = t
            new_state["last_change_t"] = t
            new_state["up_streak"] = 0
    elif headroom:
        forecast = evidence.get("forecast_replicas")
        floor = max(cfg.min_replicas, int(forecast or 0))
        if target <= floor:
            reason = "at_floor"
        elif new_state["down_streak"] < cfg.down_consecutive:
            reason = "headroom_building"
        elif (last_change is not None
                and t - last_change < cfg.cooldown_down_s):
            reason = "cooldown_down"
        else:
            action = SCALE_DOWN
            reason = "headroom"
            to = max(target - cfg.down_step, floor)
            new_state["last_change_t"] = t
            new_state["down_streak"] = 0
    return action, reason, to, new_state


def replay_decision(rec: dict) -> tuple:
    """Re-run one journaled ``autoscale_decision`` record through
    :func:`decide`, purely from its own evidence/pre-state/spec —
    the shadow-replay unit ``scripts/obs_watch.py --autoscale`` diffs
    against what the journal says Helm actually did. Returns
    ``(action, reason, to_replicas)``."""
    cfg = parse_spec(rec.get("spec", ""))
    action, reason, to, _ = decide(
        cfg, rec["evidence"], rec["state"], float(rec["t"]))
    return action, reason, int(to)


def _fresh_state() -> dict:
    return {"up_streak": 0, "down_streak": 0,
            "last_up_t": None, "last_change_t": None}


class Autoscaler:
    """The decision engine: tracks pressure evidence, consults the
    watchtower's burn windows, runs :func:`decide` on a debounced
    cadence, and journals/emits every outcome. Deliberately fleet-
    agnostic — :class:`FleetAutoscaler` binds it to a live fleet,
    :class:`SimController` to the Skyline discrete-event model.

    ``feed_tower=True`` forwards every observed event into the
    attached tower (simulation: the Autoscaler owns a private
    Watchtower). Live, the global tower is fed by its own hooks and
    Helm only *reads* its burn windows — never double-feed one."""

    def __init__(self, config: Optional[AutoscaleConfig] = None, *,
                 tower=None, feed_tower: bool = False,
                 forecast_replicas: Optional[int] = None,
                 metrics=None, spec: str = "") -> None:
        self.cfg = config or AutoscaleConfig()
        self.spec = spec
        self.metrics = metrics
        self.forecast_replicas = forecast_replicas
        self._tower = tower
        self._feed_tower = feed_tower
        self.decisions: list[Decision] = []
        self.state = _fresh_state()
        # journal-continuity anchors: a recovered coordinator resumes
        # seq numbering past the persisted journal (resume_from) and
        # stamps its own incarnation into every new record
        self.seq_offset = 0
        self.coordinator_incarnation = 0
        self._last_eval_t: Optional[float] = None
        self._queue_frac = 0.0
        self._kv_free_frac = 1.0
        # non-default pools (disagg prefill): each carries its own
        # hysteresis state, debounce anchor, and pressure sample; the
        # attributes above remain the DEFAULT_POOL's (back-compat)
        self._pool_states: dict[str, dict] = {}
        self._pool_eval_t: dict[str, float] = {}
        self._pool_pressure: dict[str, tuple] = {}
        # instruments register lazily on the first decision so an
        # armed-but-idle Helm leaves the registry untouched
        self._g_target = None
        self._g_ready = None
        self._g_burn = None
        self._c_decisions = None

    # -- evidence intake ---------------------------------------------------

    def observe(self, ev: dict) -> None:
        """Watchtower-shaped event intake: ``serve_round`` events
        update the instantaneous queue/KV fractions; with
        ``feed_tower`` every event also drives the attached tower's
        burn windows (the simulation path)."""
        if self._feed_tower and self._tower is not None:
            self._tower.observe(ev)
        if ev.get("ev") == "serve_round" and ev.get("queue_max"):
            self._queue_frac = (float(ev["queue_depth"])
                                / float(ev["queue_max"]))
            if ev.get("kv_total"):
                self._kv_free_frac = (float(ev["kv_free"])
                                      / float(ev["kv_total"]))

    def set_pressure(self, *, queue_frac: float, kv_free_frac: float,
                     pool: str = DEFAULT_POOL) -> None:
        """Authoritative fleet-wide pressure (from
        :func:`serve.router.fleet_pressure`) — overrides the last
        single-replica ``serve_round`` sample. ``pool=`` scopes the
        sample to one disaggregated pool's evidence stream."""
        if pool == DEFAULT_POOL:
            self._queue_frac = float(queue_frac)
            self._kv_free_frac = float(kv_free_frac)
        else:
            self._pool_pressure[pool] = (float(queue_frac),
                                         float(kv_free_frac))

    def _state_for(self, pool: str) -> dict:
        if pool == DEFAULT_POOL:
            return self.state
        return self._pool_states.setdefault(pool, _fresh_state())

    def _set_state_for(self, pool: str, state: dict) -> None:
        if pool == DEFAULT_POOL:
            self.state = state
        else:
            self._pool_states[pool] = state

    # -- evaluation --------------------------------------------------------

    def maybe_evaluate(self, t: float, *, ready: int, target: int,
                       pool: str = DEFAULT_POOL) -> Optional[Decision]:
        """Debounced :meth:`evaluate` — at most one decision per
        ``eval_interval_s`` of *event* time *per pool*. Returns None
        between evaluations."""
        last = (self._last_eval_t if pool == DEFAULT_POOL
                else self._pool_eval_t.get(pool))
        if last is not None and t - last < self.cfg.eval_interval_s:
            return None
        if pool == DEFAULT_POOL:
            self._last_eval_t = t
        else:
            self._pool_eval_t[pool] = t
        return self.evaluate(t, ready=ready, target=target, pool=pool)

    def evaluate(self, t: float, *, ready: int, target: int,
                 pool: str = DEFAULT_POOL) -> Decision:
        """Snapshot the evidence, run :func:`decide`, journal and emit
        the outcome. The journaled ``state`` is the PRE-decision
        hysteresis state so the record replays standalone; on a
        disaggregated fleet each pool chains its own state while seq
        stays contiguous across pools (one journal, interleaved)."""
        burn = (self._tower.burn_rates(t)
                if self._tower is not None else {})
        if pool == DEFAULT_POOL:
            queue_frac, kv_free_frac = self._queue_frac, \
                self._kv_free_frac
        else:
            queue_frac, kv_free_frac = self._pool_pressure.get(
                pool, (0.0, 1.0))
        evidence = {
            "burn": burn,
            "queue_frac": round(queue_frac, 6),
            "kv_free_frac": round(kv_free_frac, 6),
            "ready": int(ready),
            "target": int(target),
            "forecast_replicas": self.forecast_replicas,
        }
        state = self._state_for(pool)
        pre_state = dict(state)
        action, reason, to, new_state = decide(
            self.cfg, evidence, state, t)
        self._set_state_for(pool, new_state)
        d = Decision(
            seq=self.seq_offset + len(self.decisions),
            t=round(float(t), 6),
            action=action, reason=reason, from_replicas=int(ready),
            to_replicas=int(to), evidence=evidence, state=pre_state,
            spec=self.spec,
            coordinator_incarnation=self.coordinator_incarnation,
            pool=pool)
        self.decisions.append(d)
        self._emit(d)
        return d

    def resume_from(self, records: list) -> None:
        """Continue a persisted decision journal instead of forking it.

        ``records`` are the parsed ``autoscale_decision`` dicts a prior
        coordinator journaled (same shape :func:`replay_decision`
        takes). The journaled ``state`` is PRE-decision, so the resumed
        hysteresis state is re-derived by running the last record back
        through :func:`decide` — exactly the post-state an
        uninterrupted Autoscaler would carry. Sequence numbers continue
        from the journal's tail and the debounce anchor is the last
        journaled event time, so the concatenated journal (old lines +
        new lines) is indistinguishable from one life's: seq contiguous
        and every record's ``state`` equal to its predecessor's
        post-state across the restart boundary."""
        if not records:
            return
        by_pool: dict[str, dict] = {}
        for rec in records:  # last record per pool wins
            by_pool[rec.get("pool", DEFAULT_POOL)] = rec
        for pool, last in by_pool.items():
            cfg = parse_spec(last.get("spec", ""))
            _, _, _, post = decide(cfg, last["evidence"],
                                   last["state"], float(last["t"]))
            self._set_state_for(pool, post)
            if pool == DEFAULT_POOL:
                self._last_eval_t = float(last["t"])
            else:
                self._pool_eval_t[pool] = float(last["t"])
        self.seq_offset = max(int(r["seq"]) for r in records) + 1

    def _emit(self, d: Decision) -> None:
        """Every decision lands in the flight ring FIRST (lint-
        enforced: a crash right after a scaling action must still show
        the decision post-mortem), then the lazily-registered metrics
        and the JSONL stream."""
        flight.record("autoscale", d.action,
                      note=f"{d.reason} ready={d.from_replicas} "
                           f"target={d.evidence['target']}"
                           f"->{d.to_replicas}")
        self._ensure_instruments()
        self._g_target.set(float(d.to_replicas))
        self._g_ready.set(float(d.from_replicas))
        self._c_decisions.inc(action=d.action, reason=d.reason)
        for slo in sorted(d.evidence.get("burn", {})):
            b = d.evidence["burn"][slo]
            self._g_burn.set(float(b["fast"]), slo=slo, window="fast")
            self._g_burn.set(float(b["slow"]), slo=slo, window="slow")
        if self.metrics is not None:
            self.metrics.emit("autoscale_decision", **d.as_dict())
        if d.action != HOLD:
            log.info("helm %s -> %d replicas (%s)", d.action,
                     d.to_replicas, d.reason)

    def _ensure_instruments(self) -> None:
        if self._g_target is not None:
            return
        reg = get_registry()
        self._g_target = reg.gauge(
            "autoscale_replicas_target",
            "helm size intent (last decision's to_replicas)")
        self._g_ready = reg.gauge(
            "autoscale_replicas_ready",
            "READY replicas at the last helm evaluation")
        self._c_decisions = reg.counter(
            "autoscale_decisions_total", "helm decisions by outcome",
            labels=("action", "reason"))
        self._g_burn = reg.gauge(
            "autoscale_burn_input",
            "per-SLO burn rates helm last decided on",
            labels=("slo", "window"))

    # -- introspection -----------------------------------------------------

    def journal_jsonl(self) -> str:
        """The full decision journal, one canonical JSON per line —
        the unit the determinism tests diff byte-for-byte."""
        return "\n".join(d.as_json() for d in self.decisions)

    def summary(self) -> dict:
        by_action: dict[str, int] = {}
        for d in self.decisions:
            by_action[d.action] = by_action.get(d.action, 0) + 1
        return {
            "decisions": len(self.decisions),
            "by_action": by_action,
            "target": (self.decisions[-1].to_replicas
                       if self.decisions else None),
            "forecast_replicas": self.forecast_replicas,
        }


class SimController:
    """Adapter between :func:`obs.capacity.simulate_autoscaled_fleet`
    and an :class:`Autoscaler`. Duck-typed on the capacity side
    (``feed`` / ``desired``) so :mod:`obs.capacity` never imports this
    module — the obs package reaches serve code lazily only."""

    def __init__(self, scaler: Autoscaler, *, target: int) -> None:
        self.scaler = scaler
        self.target = int(target)

    def feed(self, ev: dict) -> None:
        self.scaler.observe(ev)

    def desired(self, t: float, ready: int, *,
                queue_frac: float = 0.0,
                kv_free_frac: float = 1.0) -> Optional[int]:
        """One control tick at sim time ``t`` with the service model's
        own pressure fractions; returns the new replica target when
        the policy acts, None on hold/debounce."""
        self.scaler.set_pressure(queue_frac=queue_frac,
                                 kv_free_frac=kv_free_frac)
        d = self.scaler.maybe_evaluate(t, ready=int(ready),
                                       target=self.target)
        if d is not None and d.action != HOLD:
            self.target = d.to_replicas
            return d.to_replicas
        return None


class FleetAutoscaler:
    """Helm bound to a live :class:`serve.fleet.Fleet`: each
    :meth:`step` refreshes fleet-wide pressure from the router's own
    gauges, consults the watchtower's burn windows, and applies any
    resulting decision through :meth:`Fleet.scale_to`. Drive it from
    the thread that owns the fleet (a serving front-end's poll loop,
    the process fleet's supervision pass) — never from a replica
    worker, which must not take the fleet lock."""

    def __init__(self, fleet, scaler: Autoscaler) -> None:
        self.fleet = fleet
        self.scaler = scaler

    def step(self, now: Optional[float] = None) -> Optional[Decision]:
        """One control tick; returns the decision (None when
        debounced). ``now`` defaults to wall time for live use; pass
        trace-relative time for deterministic replays. On a
        disaggregated fleet this is the first of :meth:`step_all`'s
        per-pool decisions — callers that journal every decision
        should use :meth:`step_all`."""
        decisions = self.step_all(now)
        return decisions[0] if decisions else None

    def step_all(self, now: Optional[float] = None) -> list:
        """One control tick across every scalable pool; returns the
        decisions made (empty when every pool debounced).

        Fleets that expose ``scalable_pools()`` (the disaggregated
        process fleet) get one decision per pool — each from that
        pool's own :func:`serve.router.fleet_pressure` evidence and
        hysteresis chain, applied through
        ``scale_to(n, reason=, pool=)`` (the Breakwater satellite:
        prefill queue-depth pressure grows the prefill pool). Fleets
        without pools keep the legacy single-target path unchanged."""
        t = time.time() if now is None else now
        pools_fn = getattr(self.fleet, "scalable_pools", None)
        pools = list(pools_fn()) if pools_fn is not None else []
        if not pools:
            pressure = _router.fleet_pressure(self.fleet.replicas)
            self.scaler.set_pressure(
                queue_frac=pressure["queue_frac"],
                kv_free_frac=pressure["kv_free_frac"])
            d = self.scaler.maybe_evaluate(
                t, ready=pressure["ready"],
                target=self.fleet.target_replicas)
            if d is not None and d.action != HOLD:
                self.fleet.scale_to(d.to_replicas, reason=d.reason)
            return [d] if d is not None else []
        decisions = []
        for pool in pools:
            pressure = _router.fleet_pressure(self.fleet.replicas,
                                              role=pool)
            self.scaler.set_pressure(
                queue_frac=pressure["queue_frac"],
                kv_free_frac=pressure["kv_free_frac"], pool=pool)
            d = self.scaler.maybe_evaluate(
                t, ready=pressure["ready"],
                target=self.fleet.pool_target(pool), pool=pool)
            if d is None:
                continue
            if d.action != HOLD:
                self.fleet.scale_to(d.to_replicas, reason=d.reason,
                                    pool=pool)
            decisions.append(d)
        return decisions


# -- process-global arming (mirrors obs.watchtower / runtime.chaos) --------

_helm: Optional[FleetAutoscaler] = None


def maybe_init(spec: Optional[str] = None, *, fleet=None,
               forecast_replicas: Optional[int] = None,
               metrics=None) -> bool:
    """Arm Helm for this process when ``TPUNN_AUTOSCALE`` (or an
    explicit ``spec``) says so AND a fleet is provided to act on.
    The burn-rate source is the process-global watchtower when armed
    (Helm reads its windows; it never feeds them — the watchtower's
    own hooks do). Returns True when armed."""
    global _helm
    raw = spec if spec is not None else os.environ.get(ENV_AUTOSCALE, "")
    raw = (raw or "").strip()
    # "0"/"off"/"false" = explicitly disarmed (the TPUNN_* convention)
    if raw in ("", "0", "off", "false") or fleet is None:
        return False
    cfg = parse_spec(raw)
    tower = watchtower.tower() if watchtower.enabled() else None
    scaler = Autoscaler(cfg, tower=tower, feed_tower=False,
                        forecast_replicas=forecast_replicas,
                        metrics=metrics, spec=raw)
    _helm = FleetAutoscaler(fleet, scaler)
    log.info("helm armed: %s", raw)
    return True


def enabled() -> bool:
    return _helm is not None


def helm() -> Optional[FleetAutoscaler]:
    return _helm


def reset() -> None:
    """Disarm (tests)."""
    global _helm
    _helm = None


def on_serve_round(round_: int, wall_s: float, *, queue_depth: int,
                   queue_max: int, kv_free: int, kv_total: int) -> None:
    """Serving-engine per-round hook (instantaneous queue/KV evidence
    between control ticks). Called from ``ServingEngine.step`` right
    after the watchtower's hook — never from the ``_decode_round``
    hot loop."""
    if _helm is None:
        return
    _helm.scaler.observe({"ev": "serve_round", "t": time.time(),
                          "round": int(round_),
                          "wall_s": float(wall_s),
                          "queue_depth": int(queue_depth),
                          "queue_max": int(queue_max),
                          "kv_free": int(kv_free),
                          "kv_total": int(kv_total)})
