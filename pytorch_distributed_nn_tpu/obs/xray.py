"""Xray: anomaly-triggered device profiling + per-op attribution.

The rest of the obs stack can say *that* a run is slow — goodput
decomposition (obs/goodput.py), online pages (obs/watchtower.py),
post-mortem rings (obs/flight.py + forensics). This module answers
*why*, at the op level, on three legs:

1. **Anomaly-triggered capture** — a bounded, rate-limited
   ``jax.profiler`` capture armed via ``TPUNN_XRAY=`` (chaos-style
   ``key=value:key=value`` grammar, see :class:`XrayConfig`). A capture
   fires on demand (:func:`capture_now`), every ``every`` steps, or
   when a watchtower PAGE lands (:func:`on_page`, wired from
   ``Watchtower._raise``). Each capture spans ``steps`` train/serve
   steps, then writes ``xray_summary.json`` (+ the raw perfetto trace)
   into an ``xray_<rank>_<n>_<reason>/`` directory next to the flight
   dump, and the path is named in the triggering alert's attribution
   and in ``obs_doctor --json``. ``cooldown_s`` / ``max_captures``
   bound the cost; suppressed triggers are counted, never queued.

2. **Per-op attribution** — :func:`build_attribution` merges the
   profile's slice durations (grouped per op, collectives classified
   by :data:`_COLLECTIVE_RE`) with the analytic ``utils/flops.py``
   numbers (FLOPs spread over compute rows by time share → achieved
   FLOP/s vs the chip roofline) and cross-checks collective time
   against ``CommRecorder`` wire bytes. When no device trace exists
   (``profiler=0``, or a backend without perfetto export) the table
   falls back to the flight ring's host-side dispatch windows — the
   ``collective``/``dispatch`` events with ``t0``/``t1`` stamps — so a
   capture is never empty. Rendered by ``scripts/obs_xray.py`` and
   ``scripts/obs_report.py --xray``.

3. **Compile telemetry** — when armed, every backend compile the
   process's ``jax.monitoring`` listener hears (:mod:`obs.jitwatch`,
   which names the function) becomes ``xray_compiles_total`` /
   ``xray_compile_seconds`` updates, a ``xray/compile`` flight event,
   and a :func:`watchtower.on_compile` feed — the ``recompile_storm``
   detector names the function that keeps re-tracing mid-run.

Hooks (:func:`on_step`, :func:`on_serve_round`, :func:`on_page`,
:func:`on_wire_bytes`) follow the chaos/watchtower inert-when-unset
contract — first statement is the ``_xray is None`` bail-out, AST-
checked by tests/test_quality.py — so an unarmed run pays one ``None``
check per step. Module import stays stdlib-only (jax, numpy and
ops.collectives are imported lazily inside the functions that need
them): the capture-reading scripts must run on a dev box with nothing
but the JSON artifacts.

This module also absorbed ``utils/profiling.py`` (``xprof_trace``,
``collective_trace_seconds``, ``StepTimer``/``time_steps``,
``bus_bandwidth``), which remains as a re-export shim.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import gzip
import json
import logging
import os
import re
import threading
import time
from typing import Callable, Sequence

from pytorch_distributed_nn_tpu.obs import flight, jitwatch, scopes
from pytorch_distributed_nn_tpu.obs.registry import get_registry

log = logging.getLogger(__name__)

ENV_XRAY = "TPUNN_XRAY"

#: capture summary filename contract (scripts glob on it)
SUMMARY_NAME = "xray_summary.json"
SCOPE_MAP_NAME = "scope_map.json"


# ---------------------------------------------------------------------------
# Spec grammar (chaos/watchtower-style): TPUNN_XRAY="steps=5:cooldown_s=30"
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class XrayConfig:
    """Capture policy. Every field is a spec key."""

    every: int = 0          # capture every N steps (0 = trigger-only)
    steps: int = 3          # step window one capture spans
    max_captures: int = 3   # lifetime cap per process
    cooldown_s: float = 60.0  # min seconds between capture starts
    on_page: int = 1        # 1 = a watchtower PAGE triggers a capture
    profiler: int = 1       # 1 = real jax.profiler trace; 0 = ring-only
    perfetto: int = 1       # write perfetto_trace.json.gz (parseable)
    dir: str = ""           # capture root override (default: flight dir)


_FIELD_TYPES = {f.name: f.type for f in dataclasses.fields(XrayConfig)}


def parse_spec(spec: str) -> XrayConfig:
    """``""``/``"1"``/``"on"``/``"true"`` → defaults; otherwise
    ``key=value`` pairs joined by ``:``. Unknown keys and malformed
    values raise — an armed profiler must never silently no-op."""
    cfg = XrayConfig()
    spec = spec.strip()
    if spec.lower() in ("", "1", "on", "true"):
        return cfg
    for part in spec.split(":"):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"xray spec {spec!r}: expected key=value, got {part!r}")
        key, value = part.split("=", 1)
        key = key.strip()
        kind = _FIELD_TYPES.get(key)
        if kind is None:
            raise ValueError(
                f"xray spec {spec!r}: unknown key {key!r} "
                f"(known: {sorted(_FIELD_TYPES)})")
        try:
            if kind in (str, "str"):
                cast = value.strip()
            elif kind in (int, "int"):
                cast = int(value)
            else:
                cast = float(value)
        except ValueError:
            raise ValueError(
                f"xray spec {spec!r}: bad value {value!r} for {key!r}")
        setattr(cfg, key, cast)
    _validate(cfg)
    return cfg


def _validate(cfg: XrayConfig) -> None:
    if cfg.steps < 1:
        raise ValueError(f"xray: steps must be >= 1, got {cfg.steps}")
    if cfg.max_captures < 1:
        raise ValueError(
            f"xray: max_captures must be >= 1, got {cfg.max_captures}")
    if cfg.cooldown_s < 0:
        raise ValueError(
            f"xray: cooldown_s must be >= 0, got {cfg.cooldown_s}")
    if cfg.every < 0:
        raise ValueError(f"xray: every must be >= 0, got {cfg.every}")


# ---------------------------------------------------------------------------
# Profiling primitives (absorbed from utils/profiling.py)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def xprof_trace(log_dir: str, *, perfetto: bool = False):
    """Capture an XProf/TensorBoard trace of the enclosed steps.
    ``perfetto=True`` additionally writes ``perfetto_trace.json.gz``
    (Chrome trace-event JSON), which :func:`collective_trace_seconds`
    parses — XProf's xplane protos need the TensorBoard profile plugin
    that this container doesn't ship."""
    import jax

    jax.profiler.start_trace(log_dir, create_perfetto_trace=perfetto)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# Collective-op slice names across backends: TPU emits fusion/op names
# like 'all-reduce.3' / 'all-reduce-start'; XLA CPU emits the HLO name
# ('psum_invariant.7', 'collective-permute', ...). Python-level slices
# ('$file.py:123 fn') and paired 'end: <op>' markers are excluded.
_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast|psum|ppermute|"
    r"allreduce|allgather)", re.IGNORECASE,
)


def _newest_perfetto(log_dir: str) -> str | None:
    """Newest perfetto trace under a profiler log dir — by mtime, not
    by name: profiler run dirs are timestamp strings whose lexicographic
    order need not match creation order (clock changes, host renames,
    re-used dirs)."""
    paths = glob.glob(
        os.path.join(str(log_dir), "**", "perfetto_trace.json.gz"),
        recursive=True,
    )
    if not paths:
        return None
    return max(paths, key=os.path.getmtime)


@dataclasses.dataclass
class CollectiveTrace:
    """Profile-derived collective time (see collective_trace_seconds)."""

    total_s: float  # summed slice duration across ALL device tracks
    per_device_s: float  # total_s / device participant count
    n_events: int
    names: dict[str, float]  # per-op-name seconds (diagnostics)


def collective_trace_seconds(log_dir: str,
                             world: int) -> CollectiveTrace | None:
    """Parse the newest perfetto trace under ``log_dir`` and sum the
    durations of collective-op slices (BASELINE.json bus-bw metric,
    VERDICT r2 Missing #3: bus bandwidth derived *from profile*, not
    from wire-byte bookkeeping alone).

    Each participating device contributes its own slice per executed
    collective, so ``per_device_s = total / world`` is the average time
    one device spent inside collectives. Async pairs (TPU
    'all-reduce-start'/'-done') both count — start covers the transfer
    window, done the wait — so the figure is an upper bound on wire
    occupancy. Returns None when no
    trace file or no collective slices are found (e.g. world == 1 —
    XLA elides the collectives entirely)."""
    path = _newest_perfetto(log_dir)
    if path is None:
        return None
    with gzip.open(path) as f:
        tr = json.load(f)
    events = tr["traceEvents"] if isinstance(tr, dict) else tr
    rx = _COLLECTIVE_RE
    total_us = 0.0
    names: dict[str, float] = {}
    n = 0
    for e in events:
        name = e.get("name", "")
        if (e.get("ph") != "X" or name.startswith("$")
                or name.startswith("end: ") or not rx.search(name)):
            continue
        dur = float(e.get("dur", 0.0))
        total_us += dur
        names[name] = names.get(name, 0.0) + dur / 1e6
        n += 1
    if n == 0:
        return None
    return CollectiveTrace(
        total_s=total_us / 1e6,
        per_device_s=total_us / 1e6 / max(world, 1),
        n_events=n,
        names=names,
    )


class StepTimer:
    """Wall-clock per-step timer with device fencing."""

    def __init__(self) -> None:
        self.times: list[float] = []
        self._t0: float | None = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, *fence) -> float:
        """Record one step; ``fence`` arrays are blocked on first."""
        if fence:
            import jax

            jax.block_until_ready(fence)
        dt = time.perf_counter() - self._t0
        self.times.append(dt)
        return dt

    def summary(self) -> dict[str, float]:
        if not self.times:
            # an unstarted/empty timer must summarize, not crash
            # (np.percentile([]) raises): zeros, steps=0
            return {"steps": 0, "mean_s": 0.0, "p50_s": 0.0,
                    "p95_s": 0.0, "total_s": 0.0}
        import numpy as np

        ts = np.array(self.times)
        return {
            "steps": len(ts),
            "mean_s": float(ts.mean()),
            "p50_s": float(np.percentile(ts, 50)),
            "p95_s": float(np.percentile(ts, 95)),
            "total_s": float(ts.sum()),
        }


def time_steps(step_fn: Callable, args_fn: Callable[[int], tuple], *,
               iters: int, warmup: int = 3,
               carry_state: bool = True) -> StepTimer:
    """Time ``iters`` executions of ``step_fn``. ``args_fn(i)`` yields the
    per-step ``(state, *batch)`` args; when ``carry_state`` the returned
    state threads into the next call (the real training pattern)."""
    import jax

    state, *batch = args_fn(0)
    for i in range(warmup):
        out = step_fn(state, *batch)
        state = out[0] if carry_state else state
        _, *batch = args_fn(i + 1)
    jax.block_until_ready(state)
    timer = StepTimer()
    for i in range(iters):
        timer.start()
        out = step_fn(state, *batch)
        new_state = out[0] if carry_state else state
        timer.stop(new_state)
        state = new_state
        _, *batch = args_fn(warmup + i + 1)
    return timer


@dataclasses.dataclass
class BusBandwidth:
    wire_gbps: float  # GB/s of link traffic per device
    wire_bytes_per_step: float
    step_s: float
    records: int


def bus_bandwidth(records: Sequence, step_s: float) -> BusBandwidth:
    """Ring-accounted wire bytes per device / measured step time — the
    comparable of NCCL's busbw (nccl-tests definition)."""
    from pytorch_distributed_nn_tpu.ops import collectives as cc

    wire = cc.wire_bytes(records)
    return BusBandwidth(
        wire_gbps=wire / step_s / 1e9 if step_s > 0 else 0.0,
        wire_bytes_per_step=wire,
        step_s=step_s,
        records=len(records),
    )


# ---------------------------------------------------------------------------
# Per-op attribution
# ---------------------------------------------------------------------------

def _trace_op_rows(log_dir: str) -> list[dict]:
    """Per-op rows from the newest perfetto trace: one row per slice
    name, collectives classified by :data:`_COLLECTIVE_RE`."""
    path = _newest_perfetto(log_dir)
    if path is None:
        return []
    try:
        with gzip.open(path) as f:
            tr = json.load(f)
    except (OSError, ValueError):
        return []
    events = tr["traceEvents"] if isinstance(tr, dict) else tr
    agg: dict[str, dict] = {}
    for e in events:
        name = e.get("name", "")
        if (e.get("ph") != "X" or name.startswith("$")
                or name.startswith("end: ")):
            continue
        cat = ("collective" if _COLLECTIVE_RE.search(name)
               else "compute")
        row = agg.setdefault(name, {"op": name, "category": cat,
                                    "calls": 0, "time_s": 0.0,
                                    "nbytes": 0})
        row["calls"] += 1
        row["time_s"] += float(e.get("dur", 0.0)) / 1e6
    return list(agg.values())


def _ring_op_rows(events: list[dict]) -> list[dict]:
    """Per-op rows from flight-ring events — the host-side fallback
    when no device trace exists. ``collective`` dispatch windows and
    ``dispatch`` (fused step program) events carry ``t0``/``t1``
    stamps; trace-time records (``t1 == t0``, duration 0) still count
    calls and bytes."""
    agg: dict[tuple, dict] = {}
    for e in events:
        kind = e.get("kind")
        if kind not in ("collective", "dispatch"):
            continue
        op = str(e.get("op", "")) or kind
        cat = "collective" if kind == "collective" else "compute"
        t0, t1 = e.get("t0"), e.get("t1")
        dur = (t1 - t0) if (t0 is not None and t1 is not None) else 0.0
        row = agg.setdefault((cat, op), {"op": op, "category": cat,
                                         "calls": 0, "time_s": 0.0,
                                         "nbytes": 0})
        row["calls"] += 1
        row["time_s"] += max(float(dur), 0.0)
        row["nbytes"] += int(e.get("nbytes", 0) or 0)
    return list(agg.values())


def build_attribution(*, trace_dir: str | None = None,
                      events: list[dict] | None = None,
                      wire_bytes_per_step: float | None = None,
                      flops_per_step: float | None = None,
                      steps: int = 1,
                      peak_flops: float | None = None,
                      top: int = 16,
                      scope_map: dict | None = None) -> dict:
    """The per-op table: time share per op, analytic FLOPs spread over
    compute rows by time share (→ achieved FLOP/s, roofline fraction
    when a chip peak is known), and the collective block cross-checked
    against ``CommRecorder`` wire bytes. Prefers real trace slices;
    falls back to flight-ring dispatch windows so a ``profiler=0``
    capture still attributes.

    With ``scope_map`` (:func:`obs.scopes.build`'s maps) every row
    gains ``scope`` and ``part``, found by the slice's name among the
    noted programs' instructions; a name that is no instruction of the
    map, or one that two programs put in two parts, is ``unscoped``,
    not dropped. ``by_part`` sums every row's seconds (before the table
    is cut to ``top``) by part: time only, no FLOPs, and a slice that
    nests in another (a ``while``'s body) counts beside it, as in the
    table."""
    rows: list[dict] = []
    source = "none"
    if trace_dir:
        rows = _trace_op_rows(trace_dir)
        if rows:
            source = "trace"
    if not rows and events:
        rows = _ring_op_rows(events)
        if rows:
            source = "flight_ring"
    total = sum(r["time_s"] for r in rows)
    for r in rows:
        r["share"] = r["time_s"] / total if total > 0 else 0.0
    rows.sort(key=lambda r: (-r["time_s"], r["op"]))

    compute_t = sum(r["time_s"] for r in rows
                    if r["category"] == "compute")
    if flops_per_step and compute_t > 0:
        # no per-op FLOP counts without an HLO cost analysis pass, so
        # the analytic step total is attributed by time share — exact
        # in aggregate, approximate per row (stated in the docs)
        total_flops = float(flops_per_step) * max(int(steps), 1)
        for r in rows:
            if r["category"] != "compute" or r["time_s"] <= 0:
                continue
            r["flops"] = total_flops * (r["time_s"] / compute_t)
            r["achieved_flops_per_s"] = r["flops"] / r["time_s"]
            if peak_flops:
                r["roofline_frac"] = (r["achieved_flops_per_s"]
                                      / float(peak_flops))

    coll_t = sum(r["time_s"] for r in rows
                 if r["category"] == "collective")
    coll_b = sum(r["nbytes"] for r in rows
                 if r["category"] == "collective")
    comm: dict = {
        "collective_s": coll_t,
        "collective_share": coll_t / total if total > 0 else 0.0,
        "ring_nbytes": coll_b,
    }
    if wire_bytes_per_step is not None:
        expected = float(wire_bytes_per_step) * max(int(steps), 1)
        comm["wire_bytes_per_step"] = float(wire_bytes_per_step)
        comm["expected_wire_bytes"] = expected
        if coll_t > 0:
            comm["implied_gbps"] = expected / coll_t / 1e9
        if coll_b and expected:
            comm["ring_vs_recorder"] = coll_b / expected

    by_part: dict = {}
    if scope_map is not None:
        modules = scope_map.get("modules", {})
        for r in rows:
            r["scope"], r["part"] = scopes.lookup_name(modules, r["op"])
            by_part[r["part"]] = by_part.get(r["part"], 0.0) + r["time_s"]

    rows = rows[:max(int(top), 1)]
    return {
        "source": source,
        "total_s": total,
        "rows": rows,
        "by_part": by_part,
        "comm": comm,
        "top_op": rows[0]["op"] if rows else "",
        "top_category": rows[0]["category"] if rows else "",
        "top_share": rows[0]["share"] if rows else 0.0,
    }


def find_captures(directory) -> list[str]:
    """All capture summaries under a run dir (the doctor/report glob):
    ``xray_*/xray_summary.json`` plus a bare summary, oldest first."""
    root = str(directory)
    paths = set(glob.glob(os.path.join(root, "xray_*", SUMMARY_NAME)))
    direct = os.path.join(root, SUMMARY_NAME)
    if os.path.exists(direct):
        paths.add(direct)
    return sorted(paths, key=os.path.getmtime)


def load_capture(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def render_op_table(att: dict, *, top: int = 12) -> str:
    """Fixed-width per-op table (scripts/obs_xray.py, obs_report
    --xray)."""
    lines = [
        f"source: {att.get('source', '?')}   total "
        f"{att.get('total_s', 0.0):.4f}s   collective share "
        f"{att.get('comm', {}).get('collective_share', 0.0):.1%}",
        f"{'op':<44} {'cat':<10} {'calls':>6} {'time_s':>9} "
        f"{'share':>7} {'roofline':>8} {'part':<11} scope",
    ]
    for r in att.get("rows", [])[:top]:
        roof = r.get("roofline_frac")
        lines.append(
            f"{r['op'][:44]:<44} {r['category']:<10} {r['calls']:>6} "
            f"{r['time_s']:>9.4f} {r['share']:>7.1%} "
            f"{(f'{roof:.1%}' if roof is not None else '-'):>8} "
            f"{r.get('part', '-'):<11} {r.get('scope', '')}".rstrip())
    by_part = att.get("by_part") or {}
    if by_part:
        total = sum(by_part.values()) or 1.0
        lines.append("by_part (time only): " + "  ".join(
            f"{p} {t:.4f}s {t / total:.1%}" for p, t in sorted(
                by_part.items(), key=lambda kv: -kv[1])))
    comm = att.get("comm", {})
    if comm.get("implied_gbps") is not None:
        lines.append(
            f"comm cross-check: {comm.get('expected_wire_bytes', 0):.0f}"
            f" recorder wire bytes over {comm['collective_s']:.4f}s "
            f"collective time -> {comm['implied_gbps']:.2f} GB/s")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

def _write_json(path: str, obj: dict) -> None:
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        log.warning("xray: write of %s failed: %s", path, e)


class XrayEngine:
    """Capture policy + compile watch + attribution writer. All entry
    points take an explicit ``t`` so the rate limiter is testable with
    injected clocks; module hooks stamp ``time.time()``."""

    def __init__(self, config: XrayConfig | None = None, *,
                 rank: int | None = None,
                 base_dir=None) -> None:
        self.cfg = config or XrayConfig()
        _validate(self.cfg)
        self.rank = flight.default_rank() if rank is None else int(rank)
        self._base_dir = str(base_dir) if base_dir else ""
        self.captures: list[dict] = []
        self.suppressed: dict[str, int] = {}
        # cost context fed by the trainer / bench (cross-checks)
        self.wire_bytes_per_step: float | None = None
        self.flops_per_step: float | None = None
        self.peak_flops: float | None = None
        # compile telemetry
        self.compile_counts: dict[str, int] = {}
        self.compile_seconds_total = 0.0
        self._active: dict | None = None
        self._last_capture_t: float | None = None
        self._n_started = 0
        self._lock = threading.Lock()
        reg = get_registry()
        self._c_captures = reg.counter(
            "xray_captures_total", "profiler captures started",
            labels=("trigger",))
        self._c_suppressed = reg.counter(
            "xray_suppressed_total",
            "capture triggers dropped by the rate limiter",
            labels=("reason",))
        self._c_compiles = reg.counter(
            "xray_compiles_total", "XLA compilations observed")
        self._g_compile_s = reg.gauge(
            "xray_compile_seconds",
            "cumulative seconds spent in XLA compilation")

    # -- capture lifecycle -----------------------------------------------

    def step(self, step: int, t: float | None = None) -> None:
        """One train step / serve round: advances an active capture
        window (finishing it when it has spanned ``cfg.steps``) or
        starts an interval capture on ``cfg.every`` boundaries."""
        t = time.time() if t is None else t
        if self._active is not None:
            self._active["remaining"] -= 1
            if self._active["remaining"] <= 0:
                self._finish(t)
        elif (self.cfg.every > 0 and step > 0
                and step % self.cfg.every == 0):
            self.request_capture("interval", step=step, t=t)

    def page(self, kind: str, *, step: int = -1,
             t: float | None = None) -> str | None:
        """A watchtower PAGE landed; capture unless ``on_page=0``."""
        if not self.cfg.on_page:
            return None
        return self.request_capture(f"page:{kind}", step=step, t=t)

    def request_capture(self, reason: str, *, step: int = -1,
                        t: float | None = None) -> str | None:
        """The one choke point every trigger goes through: enforces the
        busy / lifetime / cooldown bounds, counts what it drops, and
        returns the capture directory (or None when suppressed)."""
        t = time.time() if t is None else t
        with self._lock:
            if self._active is not None:
                why = "busy"
            elif self._n_started >= self.cfg.max_captures:
                why = "max_captures"
            elif (self._last_capture_t is not None
                    and t - self._last_capture_t < self.cfg.cooldown_s):
                why = "cooldown"
            else:
                why = None
                self._last_capture_t = t
                self._n_started += 1
        if why is not None:
            self.suppressed[why] = self.suppressed.get(why, 0) + 1
            self._c_suppressed.inc(reason=why)
            return None
        return self._capture(reason, step, t, self._next_dir(reason))

    def _capture(self, reason: str, step: int, t: float,
                 cap_dir: str) -> str:
        """Start one capture window. The flight event is FIRST (AST-
        linted): if the profiler itself wedges the process, the ring
        that reaches disk already says a capture was starting."""
        flight.record("xray", "capture", step=step,
                      note=f"{reason} -> {cap_dir}")
        self._c_captures.inc(trigger=reason.split(":", 1)[0])
        profiling = False
        if self.cfg.profiler:
            try:
                import jax

                jax.profiler.start_trace(
                    cap_dir, create_perfetto_trace=bool(self.cfg.perfetto))
                profiling = True
            except Exception as e:
                log.warning(
                    "xray: profiler start failed (%s); ring-only capture",
                    e)
        self._active = {
            "reason": reason, "dir": cap_dir, "step": step,
            "t_start": t, "remaining": max(self.cfg.steps, 1),
            "profiling": profiling,
        }
        return cap_dir

    def _next_dir(self, reason: str) -> str:
        base = (self._base_dir or self.cfg.dir
                or flight.resolve_dump_dir())
        slug = re.sub(r"[^A-Za-z0-9_.=-]+", "-", reason)
        d = os.path.join(
            base, f"xray_{self.rank}_{self._n_started - 1:02d}_{slug}")
        try:
            os.makedirs(d, exist_ok=True)
        except OSError as e:
            log.warning("xray: cannot create %s (%s)", d, e)
        return d

    def _finish(self, t: float) -> dict | None:
        act, self._active = self._active, None
        if act is None:
            return None
        if act["profiling"]:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                log.warning("xray: profiler stop failed: %s", e)
        events = [e for e in flight.get_recorder().snapshot()
                  if e.get("t0") is not None
                  and e["t0"] >= act["t_start"] - 1e-3]
        peak = self.peak_flops
        if peak is None:
            try:
                from pytorch_distributed_nn_tpu.utils.flops import (
                    peak_flops_per_chip,
                )

                peak = peak_flops_per_chip()  # None off-TPU
            except Exception:
                peak = None
        # the programs noted so far, compiled again from their shapes
        # (cache hits) and read once: a capture is an operator's
        # intervention, and the one place a serving process builds it
        # (its compiles are the capture's own: not the storm detector's)
        watching = jitwatch.remove_sink(self._on_jit_event)
        try:
            scope_map = scopes.build()
        finally:
            if watching:
                self._install_compile_watch()
        att = build_attribution(
            trace_dir=act["dir"] if act["profiling"] else None,
            events=events,
            wire_bytes_per_step=self.wire_bytes_per_step,
            flops_per_step=self.flops_per_step,
            steps=max(self.cfg.steps, 1),
            peak_flops=peak,
            scope_map=scope_map,
        )
        _write_json(os.path.join(act["dir"], SCOPE_MAP_NAME), scope_map)
        summary = {
            "reason": act["reason"], "rank": self.rank,
            "trigger_step": act["step"], "t_start": act["t_start"],
            "t_end": t, "steps": max(self.cfg.steps, 1),
            "dir": act["dir"], "profiler": bool(act["profiling"]),
            "compiles": dict(self.compile_counts),
            "compile_seconds": self.compile_seconds_total,
            "attribution": att,
        }
        _write_json(os.path.join(act["dir"], SUMMARY_NAME), summary)
        flight.record(
            "xray", "capture_done", step=act["step"],
            note=f"{act['reason']} top={att['top_op'] or '?'} "
                 f"-> {act['dir']}")
        self.captures.append(summary)
        return summary

    # -- compile telemetry -----------------------------------------------

    def _install_compile_watch(self) -> None:
        """Hear the process's jit listener (idempotent)."""
        jitwatch.add_sink(self._on_jit_event)

    def _uninstall_compile_watch(self) -> None:
        jitwatch.remove_sink(self._on_jit_event)

    def _on_jit_event(self, stage: str, fun: str, seconds: float) -> None:
        if stage == "compile":
            self._on_compile(fun, seconds)

    def _on_compile(self, name: str, seconds: float) -> None:
        """One observed XLA compilation (from the jit listener, or fed
        directly in tests): counters, a flight breadcrumb, and the
        watchtower recompile_storm feed."""
        name = jitwatch.bare_name(name)
        with self._lock:
            self.compile_counts[name] = (
                self.compile_counts.get(name, 0) + 1)
            self.compile_seconds_total += float(seconds)
            total = self.compile_seconds_total
        self._c_compiles.inc()
        self._g_compile_s.set(total)
        flight.record("xray", "compile", note=f"{name} {seconds:.3f}s")
        # lazy on purpose: watchtower imports xray at module level, so
        # the reverse edge must stay out of import time
        from pytorch_distributed_nn_tpu.obs import watchtower

        watchtower.on_compile(name, seconds)

    # -- teardown ---------------------------------------------------------

    def close(self, t: float | None = None) -> None:
        """Disarm: finish any open capture and leave the jit listener."""
        if self._active is not None:
            self._finish(time.time() if t is None else t)
        self._uninstall_compile_watch()

    def summary(self) -> dict:
        return {
            "captures": len(self.captures),
            "suppressed": dict(self.suppressed),
            "compiles": dict(self.compile_counts),
            "compile_seconds": self.compile_seconds_total,
            "paths": [c["dir"] for c in self.captures],
        }


# ---------------------------------------------------------------------------
# Process-wide singleton + inert hooks (the chaos/watchtower contract)
# ---------------------------------------------------------------------------

_xray: XrayEngine | None = None


def maybe_init(spec: str | None = None, *, rank: int | None = None,
               base_dir=None) -> XrayEngine | None:
    """Arm from ``TPUNN_XRAY`` (or an explicit spec). Idempotent;
    returns None when unset / "0" — the inert path."""
    global _xray
    if _xray is not None:
        return _xray
    if spec is None:
        spec = os.environ.get(ENV_XRAY, "")
    if not spec or spec.strip() == "0":
        return None
    cfg = parse_spec(spec)
    _xray = XrayEngine(cfg, rank=rank, base_dir=base_dir)
    _xray._install_compile_watch()
    log.info("xray armed: %s", cfg)
    return _xray


def enabled() -> bool:
    return _xray is not None


def engine() -> XrayEngine | None:
    return _xray


def reset() -> None:
    """Disarm and forget (test isolation)."""
    global _xray
    if _xray is not None:
        _xray._uninstall_compile_watch()
    _xray = None


def capture_now(reason: str = "manual", step: int = -1) -> str | None:
    """On-demand capture (still rate-limited); None when unarmed or
    suppressed."""
    if _xray is None:
        return None
    return _xray.request_capture(reason, step=step)


# hooks: first statement is the bail-out (AST-linted inert fast path)

def on_step(step: int) -> None:
    """Trainer step boundary."""
    if _xray is None:
        return
    _xray.step(int(step), t=time.time())


def on_serve_round(round_idx: int) -> None:
    """Serving decode round (the serving-side step clock)."""
    if _xray is None:
        return
    _xray.step(int(round_idx), t=time.time())


def on_page(kind: str, step: int = -1):
    """A watchtower PAGE landed; returns the capture dir (or None)."""
    if _xray is None:
        return
    return _xray.page(str(kind), step=int(step), t=time.time())


def on_wire_bytes(nbytes: float) -> None:
    """Analytic wire bytes per step (CommRecorder) for the comm
    cross-check."""
    if _xray is None:
        return
    _xray.wire_bytes_per_step = float(nbytes)


def on_flops(flops_per_step: float) -> None:
    """Analytic model FLOPs per step per chip (utils/flops.py cost
    model, fed by the trainer) — what turns time shares into achieved
    FLOP/s and roofline fractions in the attribution table."""
    if _xray is None:
        return
    _xray.flops_per_step = float(flops_per_step)
