"""One general traffic generator, driven by a traffic file.

A traffic file (``benchmark/traffic/<name>.json``) fixes the *shape* of
the load: arrival process, length distributions, sharing, and its own
``schedule_seed``. The schedule (offsets and lengths) is generated from
that seed alone, the way a recorded production trace would be replayed:
it is byte-identical in every run, whatever ``--seed`` says. ``--seed``
makes only the token ids (and, elsewhere, the weights).

The arithmetic is a copy of ``serve/traffic.py``'s ``generate_trace``
(one ``random.Random`` stream; Lewis-Shedler thinning for the arrivals;
lognormal lengths from a median and a log-sigma, clamped), kept here
because later PRs may change the program and may not change the
yardstick.

Kinds:

- ``serve_open``: open loop. Arrivals over the window's seconds by a
  Poisson process at ``arrivals.rate_rps``, times optional flash crowds
  (``arrivals.flash``: a list of ``{every_s, peak, ramp_s, hold_s}``,
  renormalised so the mean rate stays ``rate_rps``).
- ``serve_closed``: ``clients`` callers, each walking its own pinned
  list of ``requests_per_client`` requests, cycling when it ends.
- ``train``: no schedule; batches come from the program's loader.

``prompt`` and ``output`` are ``{median, sigma, min, max}``.
``shared_prefix`` (optional) is ``{groups, length, share}``: a share of
requests start with one of ``groups`` fixed prefixes of ``length``
tokens (what a prefix cache is built for; the first cells set none).
"""

from __future__ import annotations

import json
import math
import random

import numpy as np


def _length(rng: random.Random, spec: dict) -> int:
    val = int(round(spec["median"]
                    * math.exp(spec["sigma"] * rng.gauss(0.0, 1.0))))
    return max(int(spec["min"]), min(val, int(spec["max"])))


def _flash_factor(t: float, flashes: list) -> float:
    f = 1.0
    for fl in flashes:
        every, peak = float(fl["every_s"]), float(fl["peak"])
        ramp, hold = float(fl.get("ramp_s", 1.0)), float(fl.get("hold_s", 0.0))
        u = t % every  # a crowd crests `ramp` after each period starts
        if u < ramp:
            f *= 1.0 + (peak - 1.0) * u / max(ramp, 1e-9)
        elif u <= ramp + hold:
            f *= peak
        elif u <= 2 * ramp + hold:
            f *= peak + (1.0 - peak) * (u - ramp - hold) / max(ramp, 1e-9)
    return f


def _flash_mean(flashes: list, horizon: float) -> float:
    n = 2000
    return sum(_flash_factor((i + 0.5) * horizon / n, flashes)
               for i in range(n)) / n


def _request(rng: random.Random, spec: dict, i: int) -> dict:
    rec = {"i": i,
           "prompt_len": _length(rng, spec["prompt"]),
           "max_new": _length(rng, spec["output"])}
    sp = spec.get("shared_prefix")
    if sp and rng.random() < float(sp["share"]):
        rec["prefix_group"] = rng.randrange(int(sp["groups"]))
        rec["prefix_len"] = int(sp["length"])
        rec["prompt_len"] = max(rec["prompt_len"], rec["prefix_len"] + 1)
    return rec


def schedule(spec: dict, seconds: float) -> list:
    """The pinned schedule of a serving traffic file: for ``serve_open``
    a list of requests with their offsets ``t``; for ``serve_closed`` a
    list (one per client) of request lists."""
    rng = random.Random(int(spec["schedule_seed"]))
    if spec["kind"] == "serve_closed":
        out, i = [], 0
        for _ in range(int(spec["clients"])):
            mine = []
            for _ in range(int(spec["requests_per_client"])):
                mine.append(_request(rng, spec, i))
                i += 1
            out.append(mine)
        return out
    if spec["kind"] != "serve_open":
        raise ValueError(f"traffic kind {spec['kind']!r} has no schedule")
    arr = spec["arrivals"]
    flashes = arr.get("flash", [])
    rate = float(arr["rate_rps"])
    norm = _flash_mean(flashes, seconds) if flashes else 1.0
    fmax = 1.0
    for fl in flashes:
        fmax *= max(float(fl["peak"]), 1.0)
    rmax = rate * fmax / norm
    trace, t = [], 0.0
    while True:
        t += rng.expovariate(rmax)
        if t >= seconds:
            break
        # the rejected draw still consumes rng state: that order is the
        # determinism contract
        if flashes and rng.random() * rmax > \
                rate * _flash_factor(t, flashes) / norm:
            continue
        rec = _request(rng, spec, len(trace))
        rec["t"] = round(t, 6)
        trace.append(rec)
    return trace


def schedule_jsonl(spec: dict, seconds: float) -> str:
    """Canonical bytes of a schedule (one sorted-keys object per line)."""
    sched = schedule(spec, seconds)
    rows = sched if spec["kind"] == "serve_open" else \
        [dict(r, client=c) for c, mine in enumerate(sched) for r in mine]
    return "".join(json.dumps(r, sort_keys=True) + "\n" for r in rows)


def prompt_tokens(rec: dict, seed: int, vocab: int) -> np.ndarray:
    """Token ids of one request, from ``--seed`` and the request's
    index: uniform over the vocabulary, so no two prompts share a
    prefix unless the schedule gave them one."""
    n = int(rec["prompt_len"])
    body = np.random.default_rng([int(seed), 1, int(rec["i"])])
    if "prefix_group" in rec:
        plen = min(int(rec["prefix_len"]), n - 1)
        pre = np.random.default_rng([int(seed), 2, int(rec["prefix_group"])])
        return np.concatenate([
            pre.integers(0, vocab, size=(plen,)),
            body.integers(0, vocab, size=(n - plen,))]).astype(np.int32)
    return body.integers(0, vocab, size=(n,)).astype(np.int32)
