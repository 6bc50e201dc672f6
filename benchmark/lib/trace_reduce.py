"""From a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU each
chip is a plane ``/device:TPU:<n>`` with three lines that matter
(looked at by hand in ``benchmark/tests/record_trace.py``'s output):

- ``XLA Modules``: one event per execution of a compiled program,
  named ``jit_<function>(<fingerprint>)``;
- ``XLA Ops``: one event per operation the core executes, named by its
  HLO text (``%fusion.3 = bf16[...] fusion(...)``);
- ``Async XLA Ops``: transfers and collectives in flight, from their
  ``-start`` to their ``-done``.

Times are nanoseconds on the device's clock. Everything below the
loader is plain interval arithmetic over ``(name, start, end)`` tuples,
so that it can be tested on a hand-made list as well as on the recorded
file in ``benchmark/tests/data``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|ragged-all-to-all)")
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.3 = ...`` -> ``fusion``; ``%all-reduce-start.1`` ->
    ``all-reduce-start``."""
    m = _OP_NAME.match(text.strip())
    return m.group(1) if m else text.split(" ", 1)[0].lstrip("%")


def opcode(text: str) -> str:
    """The HLO opcode of an operation's text: ``%psum.5 = f32[8]{0}
    all-reduce(...)`` -> ``all-reduce`` (JAX names the instruction after
    its primitive, so the name alone hides a collective)."""
    _, _, rhs = text.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else ""


def event_name(text: str) -> str:
    """What an operation is listed under: its instruction name, or its
    opcode where that is a collective and the name does not say so."""
    name, code = op_name(text), opcode(text)
    if COLLECTIVE.match(code) and not COLLECTIVE.match(name):
        return code
    return name


def module_name(text: str) -> str:
    """``jit__serve_step(1234)`` -> ``jit__serve_step``."""
    return _MODULE.match(text.strip()).group(1)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """``{device_index: {"modules": [...], "ops": [...], "async": [...]}}``
    with each entry ``(name, start_ns, end_ns)``, sorted by start."""
    from jax.profiler import ProfileData

    lines = {"XLA Modules": "modules", "XLA Ops": "ops",
             "Async XLA Ops": "async"}
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        dev = {"modules": [], "ops": [], "async": []}
        for line in plane.lines:
            key = lines.get(line.name)
            if key is None:
                continue
            for e in line.events:
                name = module_name(e.name) if key == "modules" \
                    else event_name(e.name)
                dev[key].append((name, float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)))
            dev[key].sort(key=lambda ev: ev[1])
        out[int(m.group(1))] = dev
    return out


# -- interval arithmetic --------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted, disjoint ``(start, end)`` intervals."""
    merged: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The part of the disjoint sorted intervals ``a`` not covered by
    the disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(dev: dict) -> float:
    """Time in which an operation ran on this chip's core: the union of
    the ``XLA Ops`` intervals."""
    return total(union((s, e) for _, s, e in dev["ops"]))


def idle_gaps(dev: dict, top: int = 10) -> list:
    """Idle time between consecutive program executions, summed by the
    pair of programs on either side, longest first:
    ``[["after:<a>_before:<b>", seconds], ...]``."""
    gaps: dict = defaultdict(float)
    mods = dev["modules"]
    end, last = None, None
    for name, s, e in mods:
        if end is not None and s > end:
            gaps[f"after:{last}_before:{name}"] += s - end
        if end is None or e >= end:
            end, last = e, name
    ranked = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def device_ops(dev: dict, top: int = 10) -> list:
    """Seconds by operation name, the largest first."""
    acc: dict = defaultdict(float)
    for name, s, e in dev["ops"]:
        acc[name] += e - s
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def module_seconds(dev: dict) -> dict:
    """``{program: (executions, device seconds)}`` from ``XLA Modules``."""
    acc: dict = {}
    for name, s, e in dev["modules"]:
        n, t = acc.get(name, (0, 0.0))
        acc[name] = (n + 1, t + (e - s) / 1e9)
    return acc


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def collective_exposed_ns(dev: dict) -> float:
    """Time a collective is running or in flight on this chip while no
    compute operation runs: the union of collective intervals (the
    core's own ``all-reduce``/``-start``/``-done`` operations and the
    in-flight spans of ``Async XLA Ops``) less the union of all other
    ``XLA Ops`` intervals."""
    coll = union([(s, e) for n, s, e in dev["ops"] if is_collective(n)]
                 + [(s, e) for n, s, e in dev["async"] if is_collective(n)])
    compute = union((s, e) for n, s, e in dev["ops"] if not is_collective(n))
    return total(subtract(coll, compute))


def collectives_launched(dev: dict) -> int:
    """Collective operations the core issued: synchronous ones and the
    ``-start`` half of asynchronous ones (``-done`` is the same one)."""
    return sum(1 for n, _, _ in dev["ops"]
               if is_collective(n) and not n.endswith("-done"))


def summarize(devs: dict, window_s: float) -> dict:
    """What the harness keeps of a trace: per-chip busy seconds (and
    their mean), and from chip 0 the breakdown, program times and the
    collective readings."""
    if not devs:
        raise ValueError("the trace has no /device:TPU plane")
    busy = {d: busy_ns(v) / 1e9 for d, v in devs.items()}
    d0 = devs[min(devs)]
    return dict(
        window_s=window_s,
        busy_s=sum(busy.values()) / len(busy),
        busy_s_by_chip=busy,
        modules=module_seconds(d0),
        device_ops=device_ops(d0),
        idle_gaps=idle_gaps(d0),
        collective_exposed_s=collective_exposed_ns(d0) / 1e9,
        collectives_launched=collectives_launched(d0),
    )
