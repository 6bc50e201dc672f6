"""Device time by the part of the model that spent it, in the traced
run (PR 52), beside ``host_spans.py`` and ``loop_spans.py``.

A trace names a device operation by its compiled instruction and
nothing else. The program keeps its own map from instruction to scope
(``pytorch_distributed_nn_tpu/obs/scopes.py``): it notes every program
variant it traced, with shapes and no buffers, and on request compiles
them again (hits in the persistent cache) and reads their text. This
reader asks for that map once, after the window, the check and the
reference (metrics are read last in ``run.py``), loads the traced run's
``.xplane.pb`` as ``host_spans.of_run`` finds it, keeps each ``XLA Ops``
event's whole instruction text and the ``XLA Modules`` execution that
contains it, and joins: every busy instant of chip 0 goes to the
innermost event that covers it and that event to its *part* (``mixer``,
``ffn``, ``cache_write``, ``head``; ``forward``, ``backward``,
``optimizer``, ``grad_reduce``; ``other``, ``unscoped``). The parts
partition chip 0's busy time, and the ``device_*_share`` metrics are
shares of it.

It logs once a run: the table program x part (seconds, share of chip
0's busy time, events), a training step's forward and backward by the
part of the model under them, the six largest instruction names (as
the result line's ``breakdown`` has them) by part, the ten largest
``unscoped`` instruction names, the ``ambiguous`` seconds, each program's ``cache`` and build
seconds, and each ``grad_reduce/bucket<i>``'s device seconds with the
part of them no other operation overlaps.

A run whose process noted no program (a parent commit's, which has no
``obs/scopes.py``) gives ``None`` everywhere.
"""

from __future__ import annotations

import bisect
import re
import time

from benchmark.lib import host_spans
from benchmark.lib import trace_reduce as tr
from benchmark.lib.common import log

_BUCKET = re.compile(r"grad_reduce/(bucket\d+)")


def load_ops(path: str) -> dict:
    """``{chip: {"ops": [(module, text, start, end)], "async": [...]}}``
    with the whole instruction text of every ``XLA Ops`` (and ``Async
    XLA Ops``) event and the name, without fingerprint, of the ``XLA
    Modules`` execution its start lies in ('' where none does)."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted(
            (float(e.start_ns), float(e.start_ns + e.duration_ns),
             tr.module_name(e.name))
            for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [s for s, _, _ in mods]

        def module_at(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else ""

        dev: dict = {"ops": [], "async": []}
        for key, name in (("ops", "XLA Ops"), ("async", "Async XLA Ops")):
            for e in (lines[name].events if name in lines else ()):
                s = float(e.start_ns)
                dev[key].append((module_at(s), e.name, s,
                                 s + float(e.duration_ns)))
        out[int(m.group(1))] = dev
    return out


def bucket_seconds(dev: dict, modules: dict, scopes) -> dict:
    """``{bucket: (device seconds, seconds no other operation
    overlaps)}`` for the ``grad_reduce/bucket<i>`` scopes of one chip: a
    bucket's operations on the core and its collectives in flight,
    less every operation of the core that is not the bucket's."""
    memo: dict = {}
    mine: dict = {}
    rest = []
    for key in ("ops", "async"):
        for module, text, s, e in dev[key]:
            scope = memo.get((module, text))
            if scope is None:
                scope = memo[(module, text)] = scopes.lookup(
                    modules, module, text)[1]
            m = _BUCKET.search(scope)
            if m is not None:
                mine.setdefault(m.group(1), []).append((s, e))
            elif key == "ops":
                rest.append((s, e))
    out = {}
    for bucket, ivs in mine.items():
        own = tr.union(ivs)
        others = tr.union(rest + [iv for b, v in mine.items()
                                  if b != bucket for iv in v])
        out[bucket] = (tr.total(own) / 1e9,
                       tr.total(tr.subtract(own, others)) / 1e9)
    return out


def analyze(path: str) -> dict | None:
    """The join of one trace file with the process's maps, logged once.
    ``None`` when the program has no map or noted nothing."""
    try:
        from pytorch_distributed_nn_tpu.obs import scopes
    except ImportError:
        return None
    if not scopes.noted():
        return None
    t0 = time.perf_counter()
    maps = scopes.maps()
    built = time.perf_counter() - t0
    devs = load_ops(path)
    if not devs:
        return None
    dev = devs[min(devs)]
    out = scopes.join(dev["ops"], maps)
    joined = time.perf_counter() - t0 - built
    if out["busy"] <= 0.0:
        return None
    out["buckets"] = bucket_seconds(dev, maps["modules"], scopes)
    busy = out["busy"]
    pct = lambda t: 100.0 * t / busy  # noqa: E731
    log(f"device time by part (% of chip 0's busy {busy / 1e9:.4f} s): "
        + ", ".join(f"{p} {pct(t):.3f}" for p, t in sorted(
            out["by_part"].items(), key=lambda kv: -kv[1]))
        + f"; sum {pct(sum(out['by_part'].values())):.3f}; ambiguous "
        f"{out['ambiguous'] / 1e9:.4f} s")
    for module, parts in sorted(
            out["by_program"].items(),
            key=lambda kv: -sum(t for t, _ in kv[1].values())):
        log(f"  {module or '(no program)'}: " + ", ".join(
            f"{p} {t / 1e9:.4f} s {pct(t):.2f} % {n}"
            for p, (t, n) in sorted(parts.items(),
                                    key=lambda kv: -kv[1][0])))
    if out["by_layer"]:
        log("  forward and backward by the part under them (s): "
            + ", ".join(f"{k} {t / 1e9:.4f}" for k, t in sorted(
                out["by_layer"].items(), key=lambda kv: -kv[1])))
    names = sorted(out.get("by_name", {}).items(),
                   key=lambda kv: -sum(kv[1].values()))[:6]
    if names:
        log("  the largest names by part (s): " + "; ".join(
            f"{n} " + ", ".join(f"{p} {t / 1e9:.4f}" for p, t in sorted(
                parts.items(), key=lambda kv: -kv[1]))
            for n, parts in names))
    if out["unscoped"]:
        log("  largest unscoped instructions (s): " + ", ".join(
            f"{n} {t / 1e9:.4f}" for n, t in out["unscoped"]))
    for bucket, (own, alone) in sorted(out["buckets"].items()):
        log(f"  grad_reduce/{bucket}: {own:.4f} s on the device, "
            f"{alone:.4f} s with no other operation beside it")
    progs = maps["programs"]
    log(f"scope maps: {len(dev['ops'])} events read and joined in "
        f"{joined:.2f} s; {len(progs)} programs built in {built:.2f} s "
        f"(cache: " + ", ".join(
            f"{sum(p['cache'] == c for p in progs)} {c}"
            for c in sorted({p['cache'] for p in progs})) + "); "
        + "; ".join(f"{p['program']} {p['cache']} {p['seconds']:.2f} s "
                    f"{p['instructions']}" for p in progs))
    out["build_s"] = built
    out["programs"] = progs
    return out


def of_run(run: dict) -> dict | None:
    """The analysis of a traced run's file under
    ``.bench_trace/<cell>/``, computed once a run, or None (untraced,
    no file, or a program without the map)."""
    if run.get("trace") is None:
        return None
    if "_scope_shares" not in run:
        try:
            path = tr.find_xplane(
                str(host_spans.ROOT / ".bench_trace" / run["workload"]))
        except FileNotFoundError:
            path = None
        run["_scope_shares"] = analyze(path) if path else None
    return run["_scope_shares"]


def share_pct(run: dict, part: str):
    """Chip 0's busy time the traced window spent in ``part``, in % of
    its busy time."""
    a = of_run(run)
    if a is None:
        return None
    return 100.0 * a["by_part"].get(part, 0.0) / a["busy"]
