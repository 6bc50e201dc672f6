"""Prism: per-request decoding policy for the serving engine.

The policy layer between the router and the jitted decode step. A
:class:`DecodeSpec` names *how* one request's tokens are chosen —
temperature / top-k / top-p sampling, how many parallel branches to
decode, which branch the client gets back — and rides the request from
:meth:`serve.server.InferenceServer.submit` through the scheduler into
:class:`serve.engine.ServingEngine`, where the jitted sampled step
consumes it as per-row device arrays.

Contracts (all lint- or golden-enforced):

- **inert defaults**: ``DecodeSpec()`` (temperature 0, one branch) IS
  the greedy path. A batch with no sampled row runs
  ``_serve_prefill`` / ``_serve_step`` without their ``sampling``
  argument, programs that hold nothing of this module, so greedy
  outputs, JSONL records, and Lighthouse fingerprint chains are those
  of a build without it;
- **seeded determinism**: every sampled token is drawn with a key
  derived *inside the jit* as ``fold_in(fold_in(key(seed), branch),
  step)`` — a pure function of ``(seed, branch, step)``, independent
  of batch composition, slot index, replica, or restart. Same
  ``(request, seed)`` ⇒ byte-identical streams across runs, across a
  thread fleet vs a process fleet, and across a disagg prefill→decode
  handoff (the decode leg resumes at ``step = len(prefix)``);
- **per-row masking is traced**: temperature / top_k / top_p arrive as
  ``(slots,)`` device arrays, so one compiled program serves every mix
  of greedy and sampled rows (a static per-value spec would recompile
  per distinct request). A ``temperature == 0`` row takes the greedy
  ``where`` branch and emits exactly the argmax token;
- **n-best is COW**: ``best_of`` branches share the prompt's
  refcounted KV blocks via :meth:`serve.kv_pool.KVPool.fork` and
  occupy ordinary batch rows; selection is by cumulative logprob
  (accumulated inside the jitted step, under the *model* distribution
  so greedy and sampled branches rank on the same scale).

:class:`TokenStream` is the client half of incremental streaming: the
engine's single ``_emit_chunk`` funnel feeds it, the client iterates
chunks as they land. Chunking never changes the retired fingerprint —
the Lighthouse fold runs over the full token list at retirement,
however the stream was cut.
"""

from __future__ import annotations

import dataclasses
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np

_WIRE_FIELDS = ("temperature", "top_k", "top_p", "n", "best_of", "seed")


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """How one request's tokens are chosen. Immutable; validation is
    loud at construction (chaos-grammar style) so a bad spec never
    reaches the scheduler."""

    temperature: float = 0.0  # 0.0 = greedy (argmax); seed is inert
    top_k: int = 0            # 0 = no top-k mask
    top_p: float = 0.0        # 0.0 = no nucleus mask; else (0, 1]
    n: int = 1                # completions returned (req.n_best)
    best_of: int = 0          # branches decoded; 0 = n
    seed: int = 0             # per-request RNG root

    def __post_init__(self) -> None:
        if not (self.temperature >= 0.0 and self.temperature == self.temperature):
            raise ValueError(
                f"temperature must be finite and >= 0, got "
                f"{self.temperature!r}")
        if not (isinstance(self.top_k, int) and self.top_k >= 0):
            raise ValueError(f"top_k must be an int >= 0, got "
                             f"{self.top_k!r}")
        if not 0.0 <= self.top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got "
                             f"{self.top_p!r}")
        if not (isinstance(self.n, int) and self.n >= 1):
            raise ValueError(f"n must be an int >= 1, got {self.n!r}")
        if not (isinstance(self.best_of, int) and self.best_of >= 0):
            raise ValueError(f"best_of must be an int >= 0, got "
                             f"{self.best_of!r}")
        if self.best_of and self.best_of < self.n:
            raise ValueError(
                f"best_of ({self.best_of}) must be >= n ({self.n}) — "
                f"cannot return more completions than were decoded")
        if not (isinstance(self.seed, int)
                and 0 <= self.seed < 2 ** 31):
            raise ValueError(
                f"seed must be an int in [0, 2**31), got {self.seed!r}")

    @property
    def branches(self) -> int:
        """Parallel completions actually decoded (batch rows + KV
        tails this request occupies)."""
        return self.best_of or self.n

    @property
    def sampled(self) -> bool:
        """True when this spec needs the sampled jit path. Temperature
        0 with a single branch is greedy regardless of top_k/top_p
        (the argmax token survives any top-k/top-p mask), so those
        specs keep the byte-identity fast path."""
        return not (self.temperature == 0.0 and self.branches == 1)

    def to_wire(self) -> dict:
        """Non-default fields only — the process-fleet dispatch record
        keeps its key-absent discipline (a default spec adds no key at
        all, so the wire bytes are unchanged)."""
        default = DecodeSpec()
        return {f: getattr(self, f) for f in _WIRE_FIELDS
                if getattr(self, f) != getattr(default, f)}

    @classmethod
    def from_wire(cls, d: dict) -> "DecodeSpec":
        unknown = set(d) - set(_WIRE_FIELDS)
        if unknown:
            raise ValueError(
                f"unknown DecodeSpec wire keys {sorted(unknown)!r} — "
                f"known: {list(_WIRE_FIELDS)}")
        return cls(**d)


class TokenStream:
    """Client-side iterator over one request's incremental token
    chunks. The engine's ``_emit_chunk`` funnel is the only producer
    (:func:`_feed`); :meth:`close` is idempotent and fires on every
    terminal transition, so a rejected or failed request yields an
    empty (but terminated) stream instead of a hang. One-shot:
    iterate once."""

    def __init__(self, request_id: str = "") -> None:
        self.request_id = request_id
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._closed = False
        self.chunks = 0  # chunks fed (engine-side accounting mirror)

    def _feed(self, chunk) -> None:
        """Engine-only: push one token chunk (the ``_emit_chunk``
        choke point is this method's single caller, lint-pinned)."""
        self._q.put(np.asarray(chunk, np.int32))
        self.chunks += 1

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._q.put(None)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            yield item

    def tokens(self) -> np.ndarray:
        """Drain the stream (blocking until close) and return all
        tokens concatenated — the non-incremental view."""
        chunks = list(self)
        if not chunks:
            return np.zeros((0,), np.int32)
        return np.concatenate(chunks)


# -- jit-traceable sampling math (consumed inside the engine's jits) ---


def row_keys(seeds, branches, steps):
    """Per-row PRNG keys, derived entirely on device:
    ``fold_in(fold_in(key(seed), branch), step)``. A pure function of
    the three ints — the determinism contract's whole foundation."""
    def one(seed, branch, step):
        k = jax.random.PRNGKey(seed)
        k = jax.random.fold_in(k, branch)
        return jax.random.fold_in(k, step)
    return jax.vmap(one)(seeds, branches, steps)


def _mask_one(logits, top_k, top_p):
    """One row's top-k then top-p mask with TRACED k/p (zero disables
    each). Sort-based: ``lax.top_k`` needs a static k, which would
    recompile per distinct request — a sorted copy gives the k-th
    value and the nucleus cutoff with traced parameters. Composition
    order matches :func:`inference.generate._sample`: the nucleus is
    computed over the already top-k-masked distribution."""
    v = logits.shape[-1]
    desc = jnp.sort(logits)[::-1]
    kth = desc[jnp.clip(top_k, 1, v) - 1]
    keep_k = (top_k <= 0) | (logits >= kth)
    logits = jnp.where(keep_k, logits, -jnp.inf)
    desc = jnp.where((top_k <= 0) | (desc >= kth), desc, -jnp.inf)
    probs = jax.nn.softmax(desc)
    cum = jnp.cumsum(probs)
    nucleus = cum - probs < top_p  # first sorted token always kept
    cutoff = jnp.min(jnp.where(nucleus, desc, jnp.inf))
    keep_p = (top_p <= 0.0) | (logits >= cutoff)
    return jnp.where(keep_p, logits, -jnp.inf)


def sample_rows(logits, temps, top_ks, top_ps, keys):
    """(B,) sampled tokens from (B, V) logits with per-row traced
    temperature/top_k/top_p and per-row keys. A temperature-0 row
    takes the greedy ``where`` branch — exactly the argmax, whatever
    its mask parameters say (mixed greedy+sampled batches decode each
    row correctly)."""
    def one(l, t, k, p, key):
        greedy = jnp.argmax(l)
        masked = _mask_one(l, k, p)
        scaled = masked / jnp.maximum(t, 1e-6)
        drawn = jax.random.categorical(key, scaled)
        return jnp.where(t == 0.0, greedy, drawn)
    return jax.vmap(one)(logits, temps, top_ks, top_ps, keys)


def token_logprobs(logits, toks):
    """(B,) log-probabilities of the chosen tokens under the *model*
    distribution (raw logits, before masking/scaling) — the n-best
    ranking scale, meaningful across greedy and sampled branches."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, toks[:, None], axis=1)[:, 0]
