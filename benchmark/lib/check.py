"""What decides ``correct``: the timed path against the plain reference.

Every number compared is printed beside its limit, in every run. The
limits live in the cell's own file (``benchmark/cells/<workload>.json``,
key ``limits``) and were set from readings on the chip, which
``PERF.md`` lists: above the largest a sound run gave, below the
smallest the control gave.

Serving: a sample of the requests the window finished (the longest
always in it, the rest drawn from ``--seed``); the reference runs once
over each prompt with its served tokens, and for every served token the
gap is read by which its logit lies below the reference's best at that
position. ``gap_max`` is the widest, ``gap_mean`` the mean over all
sampled tokens. ``unfinished`` counts requests due in the window that
did not come back whole, and is exact. The control reads the same gaps
for the token the int8 reference puts first.

Training: the first three steps (see ``training.first_steps``) against
the reference's: ``loss_gap`` the largest relative gap of a step's
loss, ``grad_norm_gap`` and ``delta_norm_gap`` the worst leaf's gap
between the program's norm and the reference's, over the reference's
norm of that leaf or of the median leaf, whichever is larger.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark.lib.common import log


def judge(numbers: dict, limits: dict) -> tuple:
    rows, ok = [], True
    for name, value in numbers.items():
        if name not in limits:
            raise SystemExit(f"benchmark: the cell's file has no limit "
                             f"for {name}")
        limit = float(limits[name])
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        rows.append(dict(name=name, value=float(value), limit=limit,
                         ok=good))
        log(f"check {name}: {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if good else 'NOT ok'}")
    return ok, rows


def pick_sample(finished: list, k: int, seed: int) -> list:
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda s: (
        -(len(s.prompt) + len(s.tokens)), int(s.rec["i"]), s.sent))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 4])
    picks = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [order[0]] + [rest[int(j)] for j in sorted(picks)]


def serving_numbers(cfg: dict, ref, seed: int, run: dict, k: int,
                    control: bool = False) -> tuple:
    """(the program's numbers, the control's or None)."""
    # open loop: every request due in the window; closed loop: every
    # request a caller started (told to stop, it finishes its last)
    due = run["sent"]
    whole = [s for s in due if s.ok]
    numbers = {"unfinished": float(len(due) - len(whole))}
    sample = pick_sample(whole, k, seed)
    if not sample:
        numbers.update(gap_max=float("inf"), gap_mean=float("inf"))
        return numbers, None
    seqs = []
    for s in sample:
        toks = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
        seqs.append((toks, len(s.prompt) - 1))
    t = time.perf_counter()
    rows = ref.logits(cfg, seed, seqs)
    gaps = np.concatenate([
        r.max(axis=-1) - r[np.arange(len(s.tokens)), np.asarray(s.tokens)]
        for r, s in zip(rows, sample)])
    log(f"reference: {len(sample)} requests, {len(gaps)} served tokens, "
        f"lengths {[len(q[0]) + 1 for q in seqs]}, "
        f"{time.perf_counter() - t:.1f} s; best-logit spread "
        f"{float(np.mean([r.max(-1).mean() - r.mean() for r in rows])):.3f}")
    numbers.update(gap_max=float(gaps.max()), gap_mean=float(gaps.mean()))
    ctrl = None
    if control:
        low = ref.logits(cfg, seed, seqs, quantize="int8")
        cg = np.concatenate([
            r.max(axis=-1) - r[np.arange(len(r)), lo.argmax(axis=-1)]
            for r, lo in zip(rows, low)])
        ctrl = {"unfinished": 0.0, "gap_max": float(cg.max()),
                "gap_mean": float(cg.mean())}
    return numbers, ctrl


def _worst_leaf(prog: dict, want: dict, keep=None) -> tuple:
    if set(prog) != set(want):
        raise SystemExit("benchmark: program and reference name "
                         "different leaves")
    floor = statistics.median(want.values())
    gaps = {k: abs(prog[k] - want[k]) / max(want[k], floor)
            for k in want if keep is None or k in keep}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def training_numbers(first: dict, want: dict) -> dict:
    """Adam divides a leaf's update by the root of its own second
    moment, so a leaf whose true gradient is zero (a key bias: softmax
    does not see it) moves by rounding noise at full step size. Such
    leaves (reference gradient under a thousandth of the median leaf's)
    are held to ``grad_norm_gap`` alone, not to ``delta_norm_gap``."""
    g_floor = 1e-3 * statistics.median(want["grad_norms"].values())
    live = {k for k, v in want["grad_norms"].items() if v >= g_floor}
    grad, g_leaf = _worst_leaf(first["grad_norms"], want["grad_norms"])
    delta, d_leaf = _worst_leaf(first["delta_norms"], want["delta_norms"],
                                live)
    log(f"worst leaves: gradient {g_leaf}, change {d_leaf}; "
        f"{len(want['grad_norms']) - len(live)} leaves with no gradient "
        f"to speak of")
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(first["losses"], want["losses"])),
        "grad_norm_gap": grad,
        "delta_norm_gap": delta,
    }

