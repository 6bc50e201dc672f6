"""Request scheduler: bounded admission queue + prefill/decode policy.

The serving control plane. Clients call :meth:`Scheduler.submit` from
any thread; the engine loop (one thread, :mod:`serve.engine`) calls
:meth:`next_admissions` once per decode round to pull newly admitted
requests into free batch slots, and :meth:`retire` / :meth:`fail` to
release them. Policy decisions live here so the engine stays a dumb
batch-stepper:

- **backpressure**: the waiting queue is bounded (``max_queue``); a
  submit that finds it full is rejected immediately with reason
  ``backpressure`` instead of growing an unbounded buffer the server
  then OOMs on. Chaos load-shedding (``serve_reject@p=``) and oversize
  prompts (``too_large``) reject at the same choke point;
- **anti-starvation**: admission is STRICT FIFO *per tenant* with no
  bypass, deficit-round-robin across tenants. Each tenant holds its
  own FIFO deque; admission rotates through the tenant ring taking at
  most one request per tenant per turn, so a tenant with a thousand
  queued requests cannot monopolize the prefill budget — the light
  tenant's head is at most one rotation away. Within a tenant the old
  invariant holds: if the head does not fit (batch slot or KV-pool
  reservation), nothing is admitted this round — smaller requests
  cannot leapfrog a big one forever, and with
  reservation-at-admission (:mod:`serve.kv_pool`) every running
  sequence finishes within its token budget, so every admitted request
  finishes within a bounded number of scheduler rounds (tested under
  sustained overload in tests/test_serve.py). A reserve failure breaks
  the whole round, not just the tenant — skipping to a neighbor's
  smaller request would starve the big-request tenant forever;
- **tenant quotas**: ``tenant_quotas={"name": n}`` caps a tenant's
  *live* residency (queued + running) at n; a submit past the cap is
  rejected ``tenant_quota`` at the same choke point as backpressure.
  The cap bounds concurrency, not total service: as the tenant's
  requests retire, new ones fit again — a flash crowd sheds its excess
  instead of starving its neighbors (drilled by chaos
  ``tenant_flood@tenant=...:rps=...``);
- **prefix-cache admission**: with a :class:`serve.prefix_cache
  .PrefixCache` attached, admission goes through
  :meth:`PrefixCache.admit` instead of a bare ``pool.reserve`` — a
  resident shared prefix is reserved by reference and the engine
  prefills only the suffix; retirement donates the finished sequence's
  full blocks back to the index (:meth:`retire` →
  :meth:`PrefixCache.release`);
- **interleave**: at most ``max_prefills_per_round`` queued requests
  are admitted per round. Prefill is O(prompt) compute injected into
  the decode cadence — unbounded admission would stall every running
  stream's next token behind a burst of prefills (TTFT for the new
  requests at the cost of inter-token latency for everyone else);
- **deadlines**: a request whose deadline passes while still queued is
  rejected (``deadline``) at the next round rather than prefillled into
  a batch slot it can no longer use.

Every request state change goes through :meth:`Scheduler._transition`,
which increments the ``serve_requests_total{state=}`` counter AND the
per-tenant ``serve_tenant_requests_total{tenant,state}`` counter — the
test_quality.py lint enforces that no admit/reject/retire path can
bypass the accounting. Rejections additionally bump
``serve_rejects_total{reason=}`` and land a ``serve`` event in the
flight ring, so an overloaded server's shed traffic is visible in
post-mortems, not just in client-side errors.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Optional

import numpy as np

from pytorch_distributed_nn_tpu.obs import flight, meter, trace, watchtower
from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve.decoding import DecodeSpec, TokenStream
from pytorch_distributed_nn_tpu.serve.kv_pool import KVPool

# request lifecycle (terminal states: REJECTED, DONE, FAILED)
QUEUED = "queued"
RUNNING = "running"
DONE = "done"
REJECTED = "rejected"
FAILED = "failed"

_ids = itertools.count()


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle record. ``done`` is set
    exactly once, on any terminal transition — clients block on it."""

    prompt: np.ndarray  # (L,) int32
    max_new_tokens: int
    request_id: str
    deadline_s: Optional[float] = None  # absolute time.monotonic()
    state: str = QUEUED
    reject_reason: str = ""
    tokens: Optional[np.ndarray] = None  # generated tokens, (<=n,) int32
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # timing (time.monotonic()) — TTFT/latency histograms feed on these
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    # when the prefill had filled the request's rows: its first token's
    # time but for a block decoder, whose first token is its first
    # block's commit, some rounds later
    t_prefilled: float = 0.0
    # logical-request origin times (fleet-level): a resubmitted leg —
    # failover re-admission or a disagg decode-leg rewrite — carries
    # the ORIGINAL arrival in t_origin (0.0: this leg is the arrival)
    # and, when an earlier leg already delivered the first token, that
    # token's time in t_first_origin (0.0: not delivered yet). TTFT is
    # always charged from t_origin, exactly once per logical request.
    t_origin: float = 0.0
    t_first_origin: float = 0.0
    # scheduler-round bookkeeping (the anti-starvation test's evidence)
    round_submitted: int = -1
    round_admitted: int = -1
    round_done: int = -1
    # fleet failover re-admission (serve/fleet.py): this request id was
    # already counted queued/running in its first life on a replica
    # that died — _transition must not double-count those states
    resubmitted: bool = False
    # multi-tenant serving (Mosaic): quota/fairness identity + the
    # per-request LoRA adapter; prefix_match is the PrefixMatch the
    # admission pass stored (the engine's restore/suffix-prefill input)
    tenant: str = "default"
    adapter: int = 0
    prefix_match: object = None
    # Causeway (obs/trace.py): the propagated TraceContext, or None
    # when tracing is unarmed / the request is not sampled
    trace: object = None
    # Lighthouse (obs/audit.py): the fingerprint-chain seed this leg
    # resumes from — the chain over the tokens an earlier leg already
    # emitted (failover re-admission / disagg handoff), "" for a fresh
    # request or an unarmed process
    fp_seed: str = ""
    # True while this request holds a slot in its tenant's live-quota
    # count (set on QUEUED, dropped on any terminal transition)
    quota_held: bool = False
    # Prism (serve/decoding.py): how this request's tokens are chosen.
    # None = greedy single-branch — the byte-identity default every
    # pre-Prism caller gets. decode_step0 is the sampling-RNG step this
    # leg resumes at (= tokens earlier legs already emitted: a disagg
    # decode leg or a failover re-admission continues the fold_in
    # sequence instead of restarting it).
    decode: object = None
    decode_step0: int = 0
    # incremental streaming: the TokenStream the engine's _emit_chunk
    # funnel feeds; None when the client didn't ask to stream
    stream: object = None
    # n-best results for branched requests, best-first:
    # [{"tokens": [...], "logprob": float}]; logprob is the winner's
    # cumulative logprob (req.tokens = the winner's stream)
    n_best: object = None
    logprob: float = 0.0

    @property
    def total_tokens(self) -> int:
        return len(self.prompt) + self.max_new_tokens

    @property
    def branches(self) -> int:
        """Batch rows / KV tails this request decodes in parallel."""
        return self.decode.branches if self.decode is not None else 1

    @property
    def ok(self) -> bool:
        return self.state == DONE


def branch_seq_ids(req: Request) -> list[str]:
    """Pool sequence ids for a request's decode branches. Branch 0 IS
    the request id (an n=1 request's accounting is byte-identical to
    pre-Prism); extra branches suffix ``#bK``."""
    rid = req.request_id
    return [rid] + [f"{rid}#b{k}" for k in range(1, req.branches)]


class Scheduler:
    """Admission queue + policy over a shared :class:`KVPool`."""

    def __init__(self, pool: KVPool, *, max_queue: int = 64,
                 max_seq_len: int = 0,
                 max_prefills_per_round: int = 2,
                 tenant_quotas: Optional[dict] = None,
                 prefix_cache=None) -> None:
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_prefills_per_round < 1:
            raise ValueError("max_prefills_per_round must be >= 1, got "
                             f"{max_prefills_per_round}")
        self.pool = pool
        self.max_queue = max_queue
        self.max_seq_len = int(max_seq_len)
        self.max_prefills_per_round = max_prefills_per_round
        self.tenant_quotas = dict(tenant_quotas or {})
        for tenant, quota in self.tenant_quotas.items():
            if quota < 1:
                raise ValueError(f"tenant quota must be >= 1, got "
                                 f"{quota} for {tenant!r}")
        self.prefix_cache = prefix_cache  # PrefixCache | None
        self._lock = threading.Lock()
        # per-tenant FIFO deques + the DRR rotation ring (tenant names
        # in rotation order; the front tenant has next claim)
        self._queues: dict[str, collections.deque[Request]] = {}
        self._rr: collections.deque[str] = collections.deque()
        self._queued = 0  # total waiting across tenants (max_queue cap)
        self._live: dict[str, int] = {}  # tenant -> queued + running
        self.round = 0  # advanced by the engine, one per decode round
        self.draining = False
        # of the last next_admissions pass (the engine's span reads them)
        self.last_lock_wait_us = 0
        self.last_queued = 0
        self.metrics = None  # MetricsLogger; set by the owning engine
        reg = get_registry()
        self._c_requests = reg.counter(
            "serve_requests_total", "request state transitions",
            labels=("state",))
        self._c_tenant = reg.counter(
            "serve_tenant_requests_total",
            "request state transitions, per tenant",
            labels=("tenant", "state"))
        self._c_rejects = reg.counter(
            "serve_rejects_total", "requests rejected at admission",
            labels=("reason",))
        self._g_queue = reg.gauge(
            "serve_queue_depth", "requests waiting for a batch slot")

    # -- the single state-change choke point -------------------------------

    def _transition(self, req: Request, state: str,
                    reason: str = "") -> None:
        """EVERY request state change funnels through here (lint-
        enforced): the counter can't drift from reality, and terminal
        states release the waiting client exactly once."""
        req.state = state
        # Causeway breadcrumb (inert one-comparison no-op unless
        # TPUNN_TRACE armed AND this request was sampled): every state
        # change of a traced request marks its trace, lint-pinned to
        # this one choke point
        trace.on_transition(req.trace, state,
                            request_id=req.request_id)
        # Abacus tenant binding (inert unless TPUNN_METER armed, same
        # contract): QUEUED binds request_id -> tenant BEFORE the
        # admission pass's pool reservation bills any block-seconds,
        # lint-pinned to this one choke point like the trace mark above
        meter.on_request_state(req.request_id, req.tenant, state)
        # fleet re-admission idempotency: a request re-submitted with
        # the same id after a replica death already counted its
        # queued/running transitions in its first life — one logical
        # request must land in serve_requests_total{state} once per
        # state, or the fleet's request accounting drifts up with every
        # failover. Terminal states still count (the first life never
        # reached one); rejects stay per-occurrence (each reject IS a
        # distinct shed event and already spends the TTFT budget once).
        if not (req.resubmitted and state in (QUEUED, RUNNING)):
            self._c_requests.inc(state=state)
            self._c_tenant.inc(tenant=req.tenant, state=state)
        # tenant live-residency (the quota denominator): held from
        # QUEUED until any terminal state — running requests still
        # count against their tenant's cap
        if state == QUEUED and not req.quota_held:
            req.quota_held = True
            self._live[req.tenant] = self._live.get(req.tenant, 0) + 1
        elif state in (DONE, REJECTED, FAILED) and req.quota_held:
            req.quota_held = False
            self._live[req.tenant] -= 1
        if state == REJECTED:
            req.reject_reason = reason
            self._c_rejects.inc(reason=reason)
            flight.record("serve", f"reject:{reason}", note=req.request_id)
            # a shed request spends the TTFT SLO's error budget — the
            # watchtower's burn-rate detector must see it (inert no-op
            # when TPUNN_WATCH is unset), and the JSONL stream must
            # carry it too or obs_watch replay can't reproduce the
            # burn page the live tower raised
            watchtower.on_serve_reject(req.request_id, reason,
                                       tenant=req.tenant)
            if self.metrics is not None:
                self.metrics.emit("serve_reject",
                                  request_id=req.request_id, reason=reason,
                                  tenant=req.tenant)
        if state in (DONE, REJECTED, FAILED):
            req.t_done = time.monotonic()
            req.round_done = self.round
            if req.stream is not None:
                # idempotent terminal close: the engine's final
                # _emit_chunk already closed a DONE stream; a rejected
                # or failed request terminates its (empty) stream here
                # so a streaming client never hangs on a dead request
                req.stream.close()
            req.done.set()

    # -- client side -------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               deadline_s: Optional[float] = None,
               request_id: Optional[str] = None,
               resubmit: bool = False,
               tenant: str = "default",
               adapter: int = 0,
               trace_ctx: object = None,
               t_origin: Optional[float] = None,
               t_first_origin: float = 0.0,
               fp_seed: str = "",
               decode: object = None,
               decode_step0: int = 0,
               stream: bool = False) -> Request:
        """Thread-safe admission attempt. Always returns a Request; a
        rejected one is already terminal (``done`` set, ``state ==
        REJECTED``, ``reject_reason`` says why). ``resubmit`` marks a
        fleet failover re-admission (same ``request_id`` as a request
        stranded on a dead replica): its queued/running transitions are
        not re-counted (see :meth:`_transition`). ``t_origin`` /
        ``t_first_origin`` carry the logical request's original arrival
        and (if already delivered) first-token times across legs, so
        TTFT is charged from first submit exactly once;
        ``trace_ctx`` is the Causeway context riding the leg. A
        standalone (fleet-less) submit mints its own context when
        tracing is armed."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if decode is not None and not isinstance(decode, DecodeSpec):
            raise ValueError(
                f"decode must be a serve.decoding.DecodeSpec, got "
                f"{type(decode).__name__}")
        if decode == DecodeSpec():
            # an explicit all-defaults spec IS the greedy path: drop it
            # so every downstream key-absent / byte-identity contract
            # holds trivially (the inert-defaults lint's runtime half)
            decode = None
        if decode_step0 < 0:
            raise ValueError(
                f"decode_step0 must be >= 0, got {decode_step0}")
        if stream and decode is not None and decode.branches > 1:
            raise ValueError(
                "stream=True requires a single branch (best_of/n == 1):"
                " n-best ranking needs every full stream before it can "
                "pick a winner")
        req = Request(
            prompt=prompt, max_new_tokens=int(max_new_tokens),
            request_id=request_id or f"req-{next(_ids)}",
            deadline_s=deadline_s, t_submit=time.monotonic(),
            resubmitted=bool(resubmit),
            tenant=str(tenant), adapter=int(adapter),
            t_origin=float(t_origin) if t_origin else 0.0,
            t_first_origin=float(t_first_origin),
            fp_seed=str(fp_seed),
            decode=decode, decode_step0=int(decode_step0),
        )
        if stream:
            req.stream = TokenStream(req.request_id)
        # fleet legs arrive with their context minted at Fleet.submit;
        # a bare engine/scheduler mints here (same choke point role)
        req.trace = (trace_ctx if trace_ctx is not None or resubmit
                     else trace.on_submit(req.request_id,
                                          tenant=req.tenant))
        quota = self.tenant_quotas.get(req.tenant)
        with self._lock:
            req.round_submitted = self.round
            if self.draining:
                self._transition(req, REJECTED, reason="draining")
            elif self.max_seq_len and req.total_tokens > self.max_seq_len:
                self._transition(req, REJECTED, reason="too_large")
            elif chaos.on_admit(req.request_id):
                # chaos already emitted its own flight event (emit-first
                # lint); this transition adds the scheduler's view
                self._transition(req, REJECTED, reason="chaos")
            elif quota is not None \
                    and self._live.get(req.tenant, 0) >= quota:
                self._transition(req, REJECTED, reason="tenant_quota")
            elif self._queued >= self.max_queue:
                self._transition(req, REJECTED, reason="backpressure")
            else:
                q = self._queues.get(req.tenant)
                if q is None:
                    q = self._queues[req.tenant] = collections.deque()
                    self._rr.append(req.tenant)
                q.append(req)
                self._queued += 1
                self._transition(req, QUEUED)
            self._g_queue.set(self._queued)
        return req

    # -- engine side (one thread) ------------------------------------------

    def _reserve_locked(self, head: Request) -> bool:
        """One admission's KV reservation: through the prefix cache
        when attached (shared-prefix blocks reserved by reference, the
        match stored on the request for the engine's restore pass),
        bare ``pool.reserve`` otherwise. A branched (best-of-n) head
        then COW-forks one tail per extra branch off the primary —
        all-or-nothing: a tail that doesn't fit rolls the whole
        admission back. False = backpressure."""
        if self.prefix_cache is not None:
            match = self.prefix_cache.admit(
                head.request_id, head.prompt, head.total_tokens,
                adapter=head.adapter)
            if match is None:
                return False
            head.prefix_match = match
        elif not self.pool.reserve(head.request_id, head.total_tokens):
            return False
        sids = branch_seq_ids(head)
        for k, sid in enumerate(sids):
            if k == 0:
                continue
            # THE pool.fork call site (lint-pinned): branches share the
            # primary's full prompt blocks by refcount, so n branches
            # cost one prompt block set + n tails
            fork = lambda: self.pool.fork(
                head.request_id, sid, head.total_tokens,
                shared_tokens=len(head.prompt))
            if fork():
                continue
            if self.prefix_cache is not None:
                # the tail allocates straight off the free list, which
                # may be parked in the cached ring; without the same
                # LRU reclaim admit() gives the primary, a branched
                # head wedges the whole queue once donations fill the
                # pool (nothing running -> nothing ever frees)
                short = (self.pool.blocks_for(head.total_tokens)
                         - len(head.prompt) // self.pool.block_size
                         - self.pool.free_blocks)
                if short > 0 and self.prefix_cache.make_room(short) \
                        and fork():
                    continue
            for forked in sids[1:k]:
                self.pool.free(forked)
            if self.prefix_cache is not None:
                # unpin the COW tail the admit pinned, then drop the
                # primary without donating anything new
                self.prefix_cache.finish_restore(head.prefix_match)
                head.prefix_match = None
                self.prefix_cache.abandon(head.request_id)
            else:
                self.pool.free(head.request_id)
            return False
        return True

    def next_admissions(self, free_slots: int) -> list[Request]:
        """Pop eligible requests for this round: deficit round-robin
        across tenants (one request per tenant per rotation turn),
        strict FIFO within a tenant. Each admission must fit a free
        batch slot AND reserve its worst-case KV blocks. A head that
        can't reserve ends the whole round — no bypass, across tenants
        too (that's the anti-starvation invariant, not an inefficiency
        to optimize away without replacing the fairness proof)."""
        admitted: list[Request] = []
        now = time.monotonic()
        with self._lock:
            # what the pass found: the engine's serve/next_admissions
            # span says how long it waited for submitters and how many
            # requests stood in line
            self.last_lock_wait_us = int((time.monotonic() - now) * 1e6)
            self.last_queued = self._queued
            while (self._queued and free_slots > 0
                   and len(admitted) < self.max_prefills_per_round):
                # front of the rotation with work; ring stays put so
                # an emptied tenant doesn't burn a turn
                for _ in range(len(self._rr)):
                    if self._queues[self._rr[0]]:
                        break
                    self._rr.rotate(-1)
                q = self._queues[self._rr[0]]
                if not q:
                    break
                head = q[0]
                if head.deadline_s is not None and now > head.deadline_s:
                    q.popleft()
                    self._queued -= 1
                    self._transition(head, REJECTED, reason="deadline")
                    continue
                if head.branches > free_slots:
                    break  # n-way needs n rows NOW — no bypass, same
                    # anti-starvation rule as a failed reservation
                if not self._reserve_locked(head):
                    break  # no bypass: wait for blocks to free
                q.popleft()
                self._queued -= 1
                head.t_admit = now
                head.round_admitted = self.round
                self._transition(head, RUNNING)
                admitted.append(head)
                free_slots -= head.branches
                self._rr.rotate(-1)  # this tenant's turn is spent
            self._g_queue.set(self._queued)
        return admitted

    def retire(self, req: Request, tokens: np.ndarray) -> None:
        """A sequence finished (eos or budget): release its blocks and
        hand the tokens to the waiting client. With a prefix cache the
        release is a *donation*: the full blocks covering the written
        rows (prompt + all emitted tokens except the last, whose KV row
        was never computed) are indexed and parked cached instead of
        freed. The engine has already saved those rows to the device
        block store by the time this runs."""
        req.tokens = np.asarray(tokens, np.int32)
        if self.prefix_cache is not None:
            covered = (np.concatenate([req.prompt, req.tokens[:-1]])
                       if len(req.tokens) else req.prompt)
            self.prefix_cache.release(req.request_id, covered,
                                      adapter=req.adapter)
        else:
            self.pool.free(req.request_id)
        with self._lock:
            self._transition(req, DONE)

    def release_branch(self, req: Request, seq_id: str) -> None:
        """Per-branch retirement for a best-of-n request: drop ONE
        branch's reservation the moment it hits EOS/budget while its
        siblings keep decoding (refcounted prompt blocks stay live
        until the last sharer drops). Branched releases never donate
        to the prefix radix — n near-duplicate chains would churn the
        index for no reuse win — but the primary goes through
        ``abandon`` so radix-owned prompt blocks it borrowed stay with
        their chains."""
        if self.prefix_cache is not None and seq_id == req.request_id:
            self.prefix_cache.abandon(seq_id)
        else:
            self.pool.free(seq_id)

    def finish_branches(self, req: Request, tokens, n_best: list,
                        logprob: float) -> None:
        """Terminal transition for a branched request: every branch's
        reservation was already dropped via :meth:`release_branch`;
        the engine hands over the ranked results (``tokens`` = the
        winner's stream)."""
        req.tokens = np.asarray(tokens, np.int32)
        req.n_best = n_best
        req.logprob = float(logprob)
        with self._lock:
            self._transition(req, DONE)

    def fail(self, req: Request, reason: str) -> None:
        """Evict a running sequence (engine error path). Blocks are
        freed — every branch's, for a best-of-n request (freeing an
        unknown seq id is a benign no-op, so branches that already
        retired don't double-free); the client sees FAILED, not a
        hang."""
        for sid in branch_seq_ids(req):
            if self.prefix_cache is not None and sid == req.request_id:
                self.prefix_cache.abandon(sid)
            else:
                self.pool.free(sid)
        with self._lock:
            req.reject_reason = reason
            self._transition(req, FAILED)
        flight.record("serve", f"evict:{reason}", note=req.request_id)

    def drain(self) -> int:
        """Enter drain mode: stop admitting, reject everything still
        queued (reason ``draining``) so clients unblock; running
        sequences are the engine's to finish. Returns rejected count."""
        with self._lock:
            self.draining = True
            n = self._queued
            for q in self._queues.values():
                while q:
                    self._transition(q.popleft(), REJECTED,
                                     reason="draining")
            self._queued = 0
            self._g_queue.set(0)
        return n

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return self._queued
