"""The serve loop's own account of its thread, cut to the window.

Since PR 37 the program keeps, in every run and with no profiler,

- one record a decode round (``obs.serve_loop_records``): the round's
  end on ``time.monotonic()`` (the clock of ``run["t0"]`` / ``run["t1"]``),
  its wall seconds since the round before it (``busy_s``: less the
  idle wait for work ahead of it), exclusive seconds by phase that sum
  to that wall (``next_admissions``, ``admit``,
  ``dispatch``, ``fetch``, ``round_host``, ``parked``, ``other``), and,
  where something happened, what the thread traced, lowered or compiled
  (``jit``, by phase) and the collector's seconds (``gc_s``);
- a ring of the events ``jax.monitoring`` reported (``obs.jitwatch
  .events``): end, stage, function, seconds, thread.

Both rings outlive the engine, so the readers here cut the window out
of a process that also warmed up, filled and drained. A record that
straddles an edge of the window counts by the part inside it, so the
phases of a window sum to its seconds.

A program without the rings (a parent commit), or a window that holds
no record, gives ``None`` everywhere: a reader that finds nothing says
nothing.
"""

from __future__ import annotations

from benchmark.lib.common import log

PHASES = ("next_admissions", "admit", "dispatch", "fetch", "round_host",
          "parked", "other")


def _obs():
    try:
        from pytorch_distributed_nn_tpu import obs
    except ImportError:
        return None
    return obs


def window_records(run: dict) -> list:
    """The round records of the window's serve loop: those of the
    loop with the most rounds ending in ``[t0, t1)``, and the one
    that straddles ``t1``. Empty when the program keeps none."""
    obs = _obs()
    records = getattr(obs, "serve_loop_records", None)
    t0, t1 = run.get("t0"), run.get("t1")
    if records is None or t0 is None or t1 is None or t1 <= t0:
        return []
    inside = records(t0, t1)
    if not inside:
        return []
    by_loop: dict = {}
    for r in inside:
        by_loop.setdefault(r["loop"], []).append(r)
    loop = max(by_loop, key=lambda k: len(by_loop[k]))
    after = [r for r in records(t1) if r["loop"] == loop][:1]
    return by_loop[loop] + after


def _reduce(recs: list, t0: float, t1: float) -> dict | None:
    if not recs:
        return None
    window = t1 - t0
    by_phase = dict.fromkeys(PHASES, 0.0)
    covered = 0.0
    for r in recs:
        lo, hi = max(r["t"] - r["wall_s"], t0), min(r["t"], t1)
        if hi <= lo or r["wall_s"] <= 0.0:
            continue
        part = (hi - lo) / r["wall_s"]
        covered += hi - lo
        for name, v in r["phases"].items():
            by_phase[name] = by_phase.get(name, 0.0) + v * part
    ended = [r for r in recs if t0 <= r["t"] < t1]
    longest = sorted(ended, key=lambda r: -r["busy_s"])[:3]
    gc_s = sum(r.get("gc_s", 0.0) for r in ended)
    log("serve loop, its own account of the window (% of "
        f"{window:.3f} s; {len(ended)} rounds; records cover "
        f"{100.0 * covered / window:.2f}): " + ", ".join(
            f"{p} {100.0 * by_phase[p] / window:.2f}" for p in by_phase)
        + f"; collector {gc_s * 1e3:.1f} ms")
    describe = getattr(getattr(_obs(), "goodput", None), "describe_round",
                       None)
    if describe is not None:
        for r in longest:
            log("longest rounds of the window: " + describe(r, t0))
    return dict(window_s=window, covered_s=covered, by_phase=by_phase,
                rounds=len(ended), longest=longest, gc_s=gc_s)


_reduced: dict = {}   # (t0, t1) -> the window's account, logged once


def account(run: dict) -> dict | None:
    """Seconds by phase inside the window, its rounds, its three
    longest and the collector's seconds; computed and logged once a
    run."""
    key = (run.get("t0"), run.get("t1"))
    if None in key:
        return None
    if key not in _reduced:
        if len(_reduced) >= 8:
            _reduced.clear()
        _reduced[key] = _reduce(window_records(run), *key)
    return _reduced[key]


def phase_share_pct(run: dict, phase: str):
    """``phase`` seconds of the loop's thread over the window's."""
    a = account(run)
    if a is None:
        return None
    return 100.0 * a["by_phase"].get(phase, 0.0) / a["window_s"]


def longest_round_ms(run: dict):
    a = account(run)
    if a is None or not a["longest"]:
        return None
    return 1e3 * a["longest"][0]["busy_s"]


def gc_pause_ms(run: dict):
    a = account(run)
    return None if a is None else 1e3 * a["gc_s"]


def window_jit_seconds(run: dict):
    """Trace + lower + compile seconds of the events that ended inside
    the window on a thread that runs a loop users wait on (the serve
    loop's, the one that calls ``Trainer.train``); events of other
    threads are logged beside it. 0 in a sound run."""
    jitwatch = getattr(_obs(), "jitwatch", None)
    t0, t1 = run.get("t0"), run.get("t1")
    if jitwatch is None or t0 is None or t1 is None \
            or not jitwatch.installed():
        return None
    loops = jitwatch.loop_threads()
    mine, others = [], []
    for e in jitwatch.events(t0, t1):
        (mine if e.tid in loops else others).append(e)
    if mine or others:
        def show(evs):
            return [(e.stage, e.fun, round(e.seconds, 4),
                     round(e.t - t0, 2), e.thread) for e in evs[:12]]
        log(f"jit events inside the window (stage, function, s, at s, "
            f"thread): on a loop's thread {show(mine)}; on others "
            f"{show(others)}")
    return sum(e.seconds for e in mine)
