"""Skyline traffic generator: seeded, replayable synthetic load shapes.

"Heavy traffic from millions of users" as a first-class, *measurable*
input: a traffic spec declares a rate envelope (steady or diurnal, with
optional flash crowds riding on top) plus a multi-tenant mix of
heavy-tailed prompt/output length distributions, and
:func:`generate_trace` turns it into a concrete arrival trace — every
request with an arrival offset, tenant, prompt length, output budget
and per-request prompt seed. The trace is pure data: serializable to
JSONL (:func:`trace_to_jsonl`, canonical ``sort_keys`` form, so the
same spec + seed is **byte-identical** on every machine) and replayable
against a live :class:`serve.server.InferenceServer` or
:class:`serve.fleet.Fleet` (:func:`replay_trace`), or against the
deterministic service model in :mod:`obs.capacity` for capacity
planning without an accelerator.

Spec grammar (the chaos-spec contract — ``;``-joined shapes, each
``kind@key=value:key=value``; unknown kinds/keys/bad values raise):

    TPUNN_TRAFFIC="diurnal@rps=8:duration_s=60:amplitude=0.6:period_s=30"
    TPUNN_TRAFFIC="steady@rps=4:duration_s=10;flash@at_s=5:peak=4:ramp_s=1:hold_s=2"
    TPUNN_TRAFFIC="steady@rps=8:duration_s=20;\
tenant@name=chat:weight=4:prompt=lognormal:prompt_med=24:prompt_sigma=0.7;\
tenant@name=batch:weight=1:prompt=zipf:prompt_a=1.4:prompt_max=192:out_med=48"

Shape kinds:

- ``steady`` — constant rate envelope. Keys: ``rps`` (required),
  ``duration_s``.
- ``diurnal`` — sinusoidal day/night cycle:
  ``rate(t) = rps * (1 + amplitude * sin(2π(t/period_s + phase)))``.
  Keys: ``rps`` (required), ``duration_s``, ``amplitude``,
  ``period_s``, ``phase``.
- ``flash`` — a flash crowd *multiplier* on the base envelope: ramps
  linearly 1→``peak`` over ``ramp_s`` ending at ``at_s``, holds
  ``peak`` for ``hold_s``, ramps back down over ``ramp_s``. Several
  ``flash`` shapes compose multiplicatively. Keys: ``at_s`` (required),
  ``peak`` (required), ``ramp_s``, ``hold_s``.
- ``tenant`` — one tenant class in the mix, picked per-arrival with
  probability ∝ ``weight``. Length distributions per tenant:
  ``prompt``/``out`` ∈ {``lognormal``, ``zipf``} with
  ``prompt_med``/``prompt_sigma`` (lognormal: median, log-σ) or
  ``prompt_a`` (zipf exponent, heavy tail over 1..``prompt_max``), and
  the ``out_*`` twins; ``prompt_min``/``prompt_max``/``out_min``/
  ``out_max`` clamp. ``prefix_len``/``n_prefixes`` model shared
  system prompts: each arrival's prompt starts with one of the
  tenant's ``n_prefixes`` (default 1) fixed seeded prefixes of
  ``prefix_len`` tokens (picked uniformly per arrival), followed by a
  unique suffix — the load shape prefix caching is built for.
  Prism decode-policy keys (serve/decoding.py): ``temperature=`` /
  ``n=`` mark a tenant's requests sampled / best-of-n (each record
  then carries an arithmetic per-arrival ``decode_seed``, so replays
  reproduce the same sampled streams byte-for-byte); ``stream=p``
  flags each arrival streaming with probability ``p`` (one extra
  seeded draw, ONLY for tenants that set the key — the ``prefix_len``
  byte-identity precedent: older specs generate byte-identical
  traces). Keys: ``name`` (required), ``weight``, dist keys, prefix
  keys, decode keys.

Arrivals are a non-homogeneous Poisson process sampled by thinning
(Lewis-Shedler) from a single ``random.Random(seed)`` stream — exact
for any bounded rate envelope, and deterministic because *every* random
decision (candidate gaps, thinning accepts, tenant picks, lengths)
comes from that one seeded stream in a fixed order.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import logging
import math
import os
import random
import time
import zlib
from typing import Callable, Optional

import numpy as np

log = logging.getLogger(__name__)

ENV_TRAFFIC = "TPUNN_TRAFFIC"

TRAFFIC_KINDS = ("steady", "diurnal", "flash", "tenant")

# typed key tables (the chaos parse_spec contract: every key is named
# here or the spec fails loudly)
_INT_KEYS = ("prompt_min", "prompt_max", "out_min", "out_max",
             "prefix_len", "n_prefixes", "n")
_FLOAT_KEYS = ("rps", "duration_s", "amplitude", "period_s", "phase",
               "at_s", "peak", "ramp_s", "hold_s", "weight",
               "prompt_med", "prompt_sigma", "prompt_a",
               "out_med", "out_sigma", "out_a",
               "temperature", "stream")
_STR_KEYS = ("name", "prompt", "out")

_DISTS = ("lognormal", "zipf")


@dataclasses.dataclass
class Shape:
    """One parsed ``kind@...`` clause."""

    kind: str
    args: dict

    def describe(self) -> str:
        body = ":".join(f"{k}={v}" for k, v in sorted(self.args.items()))
        return f"{self.kind}@{body}" if body else self.kind


def _validate(shape: Shape) -> None:
    a = shape.args
    need = {"steady": ("rps",), "diurnal": ("rps",),
            "flash": ("at_s", "peak"), "tenant": ("name",)}[shape.kind]
    for key in need:
        if key not in a:
            raise ValueError(
                f"traffic shape {shape.kind!r} requires key {key!r} "
                f"(got {sorted(a)})")
    if a.get("rps", 1.0) <= 0:
        raise ValueError(f"traffic {shape.kind!r}: rps must be > 0")
    if not 0.0 <= a.get("amplitude", 0.0) < 1.0:
        raise ValueError("traffic diurnal: amplitude must be in [0, 1) "
                         "(the envelope may not go negative)")
    if a.get("period_s", 1.0) <= 0 or a.get("duration_s", 1.0) <= 0:
        raise ValueError(f"traffic {shape.kind!r}: period_s/duration_s "
                         f"must be > 0")
    if shape.kind == "flash" and a["peak"] <= 0:
        raise ValueError("traffic flash: peak must be > 0")
    if a.get("weight", 1.0) <= 0:
        raise ValueError("traffic tenant: weight must be > 0")
    if a.get("prefix_len", 0) < 0:
        raise ValueError("traffic tenant: prefix_len must be >= 0")
    if a.get("n_prefixes", 1) < 1:
        raise ValueError("traffic tenant: n_prefixes must be >= 1")
    if "n_prefixes" in a and a.get("prefix_len", 0) <= 0:
        raise ValueError(
            "traffic tenant: n_prefixes without prefix_len is "
            "meaningless (set prefix_len > 0)")
    if a.get("temperature", 0.0) < 0:
        raise ValueError("traffic tenant: temperature must be >= 0")
    if a.get("n", 1) < 1:
        raise ValueError("traffic tenant: n must be >= 1")
    if not 0.0 <= a.get("stream", 0.0) <= 1.0:
        raise ValueError("traffic tenant: stream must be a "
                         "probability in [0, 1]")
    if "stream" in a and a.get("n", 1) > 1:
        raise ValueError(
            "traffic tenant: stream= with n > 1 is invalid — n-best "
            "ranking needs every full stream before picking a winner "
            "(the scheduler rejects the combination too)")
    for side in ("prompt", "out"):
        dist = a.get(side, "lognormal")
        if dist not in _DISTS:
            raise ValueError(
                f"traffic tenant {side}= must be one of {_DISTS}, "
                f"got {dist!r}")
        if a.get(f"{side}_a", 1.1) <= 1.0:
            raise ValueError(
                f"traffic tenant {side}_a (zipf exponent) must be > 1")
        lo = a.get(f"{side}_min", 1)
        hi = a.get(f"{side}_max", 1 << 20)
        if not 1 <= lo <= hi:
            raise ValueError(
                f"traffic tenant needs 1 <= {side}_min <= {side}_max")


def parse_spec(spec: str) -> "TrafficSpec":
    """Parse a ``TPUNN_TRAFFIC`` spec. Exactly one base envelope
    (``steady`` or ``diurnal``) is required; a typo'd spec raises — the
    chaos contract: a load test that silently generates the wrong load
    is worse than one that refuses to start."""
    shapes: list[Shape] = []
    for clause in filter(None,
                         (c.strip() for c in (spec or "").split(";"))):
        kind, _, body = clause.partition("@")
        kind = kind.strip()
        if kind not in TRAFFIC_KINDS:
            raise ValueError(f"unknown traffic shape {kind!r} in "
                             f"{spec!r}; have {TRAFFIC_KINDS}")
        args: dict = {}
        for field in filter(None, body.split(":")):
            key, eq, value = field.partition("=")
            key = key.strip()
            value = value.strip()
            if not eq:
                raise ValueError(f"malformed traffic field {field!r} "
                                 f"in {clause!r} (want key=value)")
            try:
                if key in _INT_KEYS:
                    args[key] = int(value)
                elif key in _FLOAT_KEYS:
                    args[key] = float(value)
                elif key in _STR_KEYS:
                    args[key] = value
                else:
                    raise KeyError(key)
            except KeyError:
                raise ValueError(
                    f"unknown traffic key {key!r} for shape {kind!r} "
                    f"in {spec!r}") from None
            except ValueError:
                raise ValueError(
                    f"bad value for traffic key {key!r}: {value!r}"
                ) from None
        shape = Shape(kind, args)
        _validate(shape)
        shapes.append(shape)
    bases = [s for s in shapes if s.kind in ("steady", "diurnal")]
    if len(bases) != 1:
        raise ValueError(
            f"traffic spec needs exactly one base envelope "
            f"(steady|diurnal), got {len(bases)} in {spec!r}")
    return TrafficSpec(shapes=tuple(shapes))


def maybe_from_env() -> Optional["TrafficSpec"]:
    """Parse ``TPUNN_TRAFFIC`` when set and non-"0", else None."""
    spec = os.environ.get(ENV_TRAFFIC, "").strip()
    if not spec or spec == "0":
        return None
    return parse_spec(spec)


@dataclasses.dataclass(frozen=True)
class TrafficSpec:
    """A parsed traffic spec: one base envelope + flash/tenant shapes."""

    shapes: tuple

    @property
    def base(self) -> Shape:
        return next(s for s in self.shapes
                    if s.kind in ("steady", "diurnal"))

    @property
    def flashes(self) -> list[Shape]:
        return [s for s in self.shapes if s.kind == "flash"]

    @property
    def tenants(self) -> list[Shape]:
        ts = [s for s in self.shapes if s.kind == "tenant"]
        return ts or [Shape("tenant", {"name": "default"})]

    @property
    def duration_s(self) -> float:
        return float(self.base.args.get("duration_s", 10.0))

    @property
    def base_rps(self) -> float:
        return float(self.base.args["rps"])

    @property
    def shape_name(self) -> str:
        """Report label: base kind plus a +flash marker."""
        name = self.base.kind
        if self.flashes:
            name += "+flash"
        return name

    def describe(self) -> str:
        return ";".join(s.describe() for s in self.shapes)

    # -- rate envelope ---------------------------------------------------

    def rate_at(self, t: float, *, rps_scale: float = 1.0) -> float:
        """Instantaneous offered rate (req/s) at trace time ``t``."""
        base = self.base
        rate = base.args["rps"] * rps_scale
        if base.kind == "diurnal":
            amp = base.args.get("amplitude", 0.5)
            period = base.args.get("period_s", 60.0)
            phase = base.args.get("phase", 0.0)
            rate *= 1.0 + amp * math.sin(2 * math.pi
                                         * (t / period + phase))
        for fl in self.flashes:
            at = fl.args["at_s"]
            peak = fl.args["peak"]
            ramp = fl.args.get("ramp_s", 1.0)
            hold = fl.args.get("hold_s", 0.0)
            if at - ramp <= t < at:            # ramp up
                frac = (t - (at - ramp)) / max(ramp, 1e-9)
                rate *= 1.0 + (peak - 1.0) * frac
            elif at <= t <= at + hold:          # hold the crest
                rate *= peak
            elif at + hold < t <= at + hold + ramp:  # ramp down
                frac = (t - (at + hold)) / max(ramp, 1e-9)
                rate *= peak + (1.0 - peak) * frac
        return max(rate, 0.0)

    def rate_max(self, *, rps_scale: float = 1.0) -> float:
        """Analytic upper bound on the envelope — the thinning
        majorant. Flash multipliers compose, so bound with their
        product (conservative; thinning stays exact)."""
        base = self.base
        peak = base.args["rps"] * rps_scale
        if base.kind == "diurnal":
            peak *= 1.0 + base.args.get("amplitude", 0.5)
        for fl in self.flashes:
            peak *= max(fl.args["peak"], 1.0)
        return peak


# ---------------------------------------------------------------------------
# Trace generation (all randomness from one seeded stdlib stream)
# ---------------------------------------------------------------------------


def _zipf_cdf(a: float, n: int) -> list[float]:
    weights = [k ** -a for k in range(1, n + 1)]
    total = sum(weights)
    acc, cdf = 0.0, []
    for w in weights:
        acc += w
        cdf.append(acc / total)
    return cdf


def _sample_len(rng: random.Random, args: dict, side: str,
                *, default_med: float, default_max: int) -> int:
    dist = args.get(side, "lognormal")
    lo = args.get(f"{side}_min", 1)
    hi = args.get(f"{side}_max", default_max)
    if dist == "zipf":
        a = args.get(f"{side}_a", 1.3)
        cdf = _zipf_cdf(a, hi)
        val = bisect.bisect_left(cdf, rng.random()) + 1
    else:
        med = args.get(f"{side}_med", default_med)
        sigma = args.get(f"{side}_sigma", 0.6)
        val = int(round(med * math.exp(sigma * rng.gauss(0.0, 1.0))))
    return max(lo, min(val, hi))


def generate_trace(spec: TrafficSpec, *, seed: int = 0,
                   rps_scale: float = 1.0,
                   max_requests: int = 1_000_000) -> list[dict]:
    """Spec + seed → arrival trace, deterministically. Each record:

    ``{"i", "t", "tenant", "prompt_len", "max_new", "prompt_seed"}``

    ``rps_scale`` multiplies the whole envelope — the capacity sweep's
    offered-load knob — while keeping the same seed, so rungs of one
    sweep are directly comparable shapes, not unrelated traces."""
    rng = random.Random(seed)
    tenants = spec.tenants
    cum, acc = [], 0.0
    for ten in tenants:
        acc += ten.args.get("weight", 1.0)
        cum.append(acc)
    rmax = spec.rate_max(rps_scale=rps_scale)
    duration = spec.duration_s
    trace: list[dict] = []
    t = 0.0
    while len(trace) < max_requests:
        t += rng.expovariate(rmax)
        if t >= duration:
            break
        # thinning: accept the candidate with prob rate(t)/rmax. The
        # rejected draw still consumes rng state — that ordering IS the
        # determinism contract, do not reorder draws.
        if rng.random() * rmax > spec.rate_at(t, rps_scale=rps_scale):
            continue
        ten = tenants[bisect.bisect_left(cum, rng.random() * acc)]
        idx = len(trace)
        rec = {
            "i": idx,
            "t": round(t, 6),
            "tenant": ten.args.get("name", "default"),
            "prompt_len": _sample_len(rng, ten.args, "prompt",
                                      default_med=24.0, default_max=256),
            "max_new": _sample_len(rng, ten.args, "out",
                                   default_med=16.0, default_max=128),
            "prompt_seed": (seed * 1_000_003 + idx) & 0x7FFFFFFF,
        }
        prefix_len = int(ten.args.get("prefix_len", 0))
        if prefix_len > 0:
            # shared-system-prompt shape: pick one of the tenant's
            # fixed prefixes. The extra rng draw happens ONLY for
            # prefix tenants, so specs without prefix_len generate
            # byte-identical traces to older versions.
            pidx = rng.randrange(int(ten.args.get("n_prefixes", 1)))
            tenant_ns = zlib.crc32(rec["tenant"].encode())
            rec["prefix_len"] = prefix_len
            rec["prefix_seed"] = ((seed * 1_000_033 + tenant_ns * 31
                                   + pidx) & 0x7FFFFFFF)
            # the prompt must extend past its prefix by >= 1 token
            # (a cached prefix still needs a suffix to prefill)
            rec["prompt_len"] = max(rec["prompt_len"], prefix_len + 1)
        # Prism decode-policy keys: present ONLY when the tenant set
        # them, so specs without them generate byte-identical traces.
        # decode_seed is arithmetic (prompt_seed's scheme, different
        # multiplier) — no rng draw, so it perturbs nothing.
        temp = float(ten.args.get("temperature", 0.0))
        n_best = int(ten.args.get("n", 1))
        if temp > 0.0 or n_best > 1:
            if temp > 0.0:
                rec["temperature"] = temp
            if n_best > 1:
                rec["n"] = n_best
            rec["decode_seed"] = (seed * 1_000_081 + idx) & 0x7FFFFFFF
        if "stream" in ten.args:
            # the ONE extra rng draw, only for tenants using stream=
            # (the prefix_len byte-identity precedent)
            if rng.random() < float(ten.args["stream"]):
                rec["stream"] = True
        trace.append(rec)
    return trace


# ---------------------------------------------------------------------------
# Canonical JSONL serialization (byte-identical replay unit)
# ---------------------------------------------------------------------------


def trace_to_jsonl(trace: list[dict]) -> str:
    """Canonical serialization: one ``sort_keys`` JSON object per line.
    Same spec + seed → the same bytes, on every run and machine."""
    return "".join(json.dumps(rec, sort_keys=True) + "\n"
                   for rec in trace)


def write_trace(path: str, trace: list[dict]) -> None:
    with open(path, "w") as f:
        f.write(trace_to_jsonl(trace))


def load_trace(path: str) -> list[dict]:
    trace = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                trace.append(json.loads(line))
    return trace


def prompt_tokens(rec: dict, vocab_size: int) -> np.ndarray:
    """The prompt for a trace record — derived from its
    ``prompt_seed``, so replay regenerates identical tokens without
    serializing them. Records carrying ``prefix_seed`` (the
    ``prefix_len=`` tenant grammar) start with the shared seeded
    prefix — every record with the same prefix_seed gets the same
    leading tokens, which is what makes replayed traffic exercise the
    prefix cache — followed by a per-request suffix."""
    total = int(rec["prompt_len"])
    rng = np.random.default_rng(int(rec["prompt_seed"]))
    if "prefix_seed" in rec:
        plen = min(int(rec["prefix_len"]), total - 1)
        prng = np.random.default_rng(int(rec["prefix_seed"]))
        prefix = prng.integers(0, vocab_size, size=(plen,))
        suffix = rng.integers(0, vocab_size, size=(total - plen,))
        return np.concatenate([prefix, suffix]).astype(np.int32)
    return rng.integers(0, vocab_size,
                        size=(total,)).astype(np.int32)


# ---------------------------------------------------------------------------
# Replay drivers
# ---------------------------------------------------------------------------


def replay_trace(trace: list[dict], submit: Callable,
                 *, vocab_size: int, realtime: bool = True,
                 time_scale: float = 1.0) -> list:
    """Drive a live service with a trace. ``submit(prompt, max_new)``
    adapts the target — ``lambda p, n: server.submit(p, n)`` or
    ``lambda p, n: fleet.submit(p, n)``. ``realtime=True`` sleeps to
    each record's arrival offset (``time_scale`` compresses/stretches
    the clock); ``realtime=False`` submits the backlog at once (the
    saturation probe). Returns the submit handles in trace order.

    Records carrying Prism decode keys (``temperature``/``n`` +
    ``decode_seed``, or ``stream``) submit with the matching
    ``decode=DecodeSpec(...)`` / ``stream=True`` kwargs; records
    without them call the plain two-argument form, so existing
    ``lambda p, n: ...`` adapters replay older traces unchanged."""
    from pytorch_distributed_nn_tpu.serve.decoding import DecodeSpec

    handles = []
    t0 = time.monotonic()
    for rec in trace:
        if realtime:
            wait = float(rec["t"]) / time_scale - (time.monotonic() - t0)
            if wait > 0:
                time.sleep(wait)
        kw = {}
        if "temperature" in rec or "n" in rec:
            kw["decode"] = DecodeSpec(
                temperature=float(rec.get("temperature", 0.0)),
                n=int(rec.get("n", 1)),
                seed=int(rec.get("decode_seed", 0)))
        if rec.get("stream"):
            kw["stream"] = True
        if kw:
            handles.append(submit(prompt_tokens(rec, vocab_size),
                                  int(rec["max_new"]), **kw))
        else:
            handles.append(submit(prompt_tokens(rec, vocab_size),
                                  int(rec["max_new"])))
    return handles
