"""Continuous-batching decode engine.

The data plane of the serving stack: a dense batched KV cache of
``max_slots`` rows, stepped one token per round for every active row,
with finished rows retired *mid-batch* and newly admitted requests
prefilled into the freed rows — the batch never drains to admit work.
Policy (who gets in, who waits) is the scheduler's
(:mod:`serve.scheduler`); this module only executes its decisions.

Correctness contract: greedy decode through the engine is
**bit-identical** to sequential ``inference.generate.generate`` for the
same prompt (tests/test_serve.py golden test). Both paths run the same
per-row math — prefill via :func:`inference.generate.prefill_ragged`
(batch of one) and per-round steps via the same per-row decode apply,
where every row's attention is masked to exactly its own filled cache
prefix; masked slots contribute exact 0.0 after softmax, so sharing a
batch with strangers cannot perturb a row's floats. That holds on the
CPU backend, where the golden test runs. On the TPU in bf16 it does
not (chip_smoke.py, PR 23: at Llama-3-8B widths 9 of 12 requests left
the sequential path): a row goes through programs of other shapes here
(8 slots over a 256-row cache) than there (one row, a cache of its own
length), and the compiler orders the same sums differently. Every
divergence seen was a choice between logits less than half a bf16 step
apart; chip_smoke.py gates on that margin.

Hot-loop discipline (lint-enforced): :meth:`ServingEngine._decode_round`
contains the per-round device work and performs NO host->device
transfers and no jnp/jax array construction — slot state (last token,
per-row cache depth, active mask) lives on device across rounds, and
the one device->host fetch per round (the sampled tokens the scheduler
must see to detect eos/budget) is a single ``np.asarray`` of a (slots,)
array. Slot mutations (admission, retirement) happen outside the hot
method and push the refreshed slot arrays once.

Observability: TTFT + per-token latency histograms, batch-occupancy /
queue-depth / KV-utilization gauges, one flight-ring ``serve`` event
per decode round (a wedged loop is visible to the doctor as a stalled
round counter), per-request retroactive spans when tracing is on, and
per-request ``serve_request`` JSONL records through MetricsLogger.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.inference.generate import (
    _apply_decode_ragged,
    init_cache,
)
from pytorch_distributed_nn_tpu.nn.lora import num_adapters
from pytorch_distributed_nn_tpu.obs import (
    audit,
    flight,
    meter,
    trace,
    watchtower,
    xray,
)
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import autoscale, decoding
from pytorch_distributed_nn_tpu.serve.kv_pool import KVPool
from pytorch_distributed_nn_tpu.serve.prefix_cache import PrefixCache
from pytorch_distributed_nn_tpu.serve.scheduler import (
    Request,
    Scheduler,
    branch_seq_ids,
)

# TTFT spans queueing (ms..s under load); per-token latency is ms-scale
_TTFT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                 2.5, 5.0, 10.0, 30.0)
_TOKEN_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                  0.25, 0.5, 1.0)


def _apply_prefill_at(model, params, cache, tokens, lengths, starts,
                      **extra):
    """Ragged prefill with a per-row cache-write offset: row i's KV
    lands in cache rows [starts[i], starts[i] + lengths[i]) and its
    queries attend absolute positions [0, starts[i] + t] — which is
    what prefix-cache suffix prefill needs: the restored rows
    [0, starts[i]) are already in ``cache`` and the suffix computes
    exactly the floats a full from-zero prefill would have. Returns
    ((B, V) logits at each row's LAST real suffix position, cache).
    ``extra`` forwards per-request LoRA (lora_bank + adapter_ids) so
    TransformerLM-family models never see unknown kwargs."""
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens,
        train=False, decode=True, mutable=["cache"],
        cache_positions=starts.astype(jnp.int32), **extra,
    )
    last = (lengths.astype(jnp.int32) - 1)[:, None, None]
    next_logits = jnp.take_along_axis(logits, last, axis=1)[:, 0, :]
    return next_logits, mutated["cache"]


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_prefill(model, params, cache, tokens, lengths, starts):
    """Batch-of-one (suffix) prefill + greedy first token: (1,) int32
    token, filled (1, P_pad, ...) row cache. ``starts`` (1,) int32 is
    the number of rows already restored from the prefix cache (0 for a
    miss). The argmax runs on device so the only host transfer is the
    token itself."""
    next_logits, cache = _apply_prefill_at(model, params, cache,
                                           tokens, lengths, starts)
    return jnp.argmax(next_logits, axis=-1).astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_prefill_lora(model, params, cache, tokens, lengths, starts,
                        bank, ids):
    """LoRA twin of :func:`_serve_prefill`: same math plus per-row
    adapter deltas. A separate jit (not a None-bank branch) keeps the
    base path's trace free of the bank pytree."""
    next_logits, cache = _apply_prefill_at(
        model, params, cache, tokens, lengths, starts,
        lora_bank=bank, adapter_ids=ids)
    return jnp.argmax(next_logits, axis=-1).astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_step(model, params, cache, last_tok, lengths, active):
    """One decode round over all slots: feed every row its last token
    at its own cache depth, take greedy argmax. Inactive rows still
    flow through the batched apply (a dynamic batch size would
    recompile); their tokens/depths are frozen by the ``active`` mask
    and their cache writes land in retired rows that the next
    occupant's prefill overwrites (and masks until it grows there)."""
    logits, cache = _apply_decode_ragged(model, params, cache, last_tok,
                                         lengths)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    nxt = jnp.where(active, nxt, last_tok)
    lengths = jnp.where(active, lengths + 1, lengths)
    return nxt, lengths, cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_step_lora(model, params, cache, last_tok, lengths, active,
                     bank, ids):
    """LoRA twin of :func:`_serve_step`: each row applies its own
    adapter's deltas (ids is the per-slot adapter mirror), so one
    batched decode serves every tenant's fine-tune at once."""
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, last_tok[:, None],
        train=False, decode=True, last_only=True, mutable=["cache"],
        cache_positions=lengths.astype(jnp.int32),
        lora_bank=bank, adapter_ids=ids,
    )
    nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    nxt = jnp.where(active, nxt, last_tok)
    lengths = jnp.where(active, lengths + 1, lengths)
    return nxt, lengths, mutated["cache"]


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_prefill_logits(model, params, cache, tokens, lengths, starts):
    """Sampled-path prefill twin of :func:`_serve_prefill`: returns the
    (1, V) next-token logits instead of their argmax, so the host can
    fan ONE prompt's logits into n branch first-tokens (and their
    logprobs) without a second forward. The greedy path never routes
    here — its jit (and bytes) are untouched."""
    return _apply_prefill_at(model, params, cache, tokens, lengths,
                             starts)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_prefill_logits_lora(model, params, cache, tokens, lengths,
                               starts, bank, ids):
    """LoRA twin of :func:`_serve_prefill_logits`."""
    return _apply_prefill_at(model, params, cache, tokens, lengths,
                             starts, lora_bank=bank, adapter_ids=ids)


@functools.partial(jax.jit, static_argnums=(1,))
def _sample_first(next_logits, n, temp, top_k, top_p, seed, step0):
    """First token for each of a request's ``n`` branches from one
    prefill's (1, V) logits: branch k draws with key
    ``fold_in(fold_in(key(seed), k), step0)`` — the same derivation
    the decode-step jit uses, so a branch's whole stream is one
    unbroken (seed, branch, step) sequence. Returns ((n,) int32
    tokens, (n,) float32 logprobs under the model distribution).
    ``n`` is static: one program per distinct branch count, not per
    spec."""
    row = next_logits[0]
    logits = jnp.broadcast_to(row, (n, row.shape[-1]))
    branches = jnp.arange(n, dtype=jnp.int32)
    seeds = jnp.full((n,), seed, jnp.int32)
    steps = jnp.full((n,), step0, jnp.int32)
    temps = jnp.full((n,), temp, jnp.float32)
    top_ks = jnp.full((n,), top_k, jnp.int32)
    top_ps = jnp.full((n,), top_p, jnp.float32)
    keys = decoding.row_keys(seeds, branches, steps)
    toks = decoding.sample_rows(logits, temps, top_ks, top_ps,
                                keys).astype(jnp.int32)
    return toks, decoding.token_logprobs(logits, toks)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_step_sample(model, params, cache, last_tok, lengths, active,
                       temps, top_ks, top_ps, seeds, branches, steps,
                       logprob):
    """Sampled twin of :func:`_serve_step`: the SAME ragged forward
    (greedy rows in a mixed batch still see bit-identical logits and
    take the per-row greedy ``where`` branch), then per-row seeded
    sampling with traced temperature/top_k/top_p. Per-row RNG steps
    and cumulative logprobs advance INSIDE the jit, so the hot loop
    stays transfer-free and best-of-n ranking needs no per-round
    fetch."""
    logits, cache = _apply_decode_ragged(model, params, cache, last_tok,
                                         lengths)
    keys = decoding.row_keys(seeds, branches, steps)
    drawn = decoding.sample_rows(logits, temps, top_ks, top_ps,
                                 keys).astype(jnp.int32)
    logprob = jnp.where(active,
                        logprob + decoding.token_logprobs(logits, drawn),
                        logprob)
    nxt = jnp.where(active, drawn, last_tok)
    lengths = jnp.where(active, lengths + 1, lengths)
    steps = jnp.where(active, steps + 1, steps)
    return nxt, lengths, steps, logprob, cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_step_sample_lora(model, params, cache, last_tok, lengths,
                            active, bank, ids, temps, top_ks, top_ps,
                            seeds, branches, steps, logprob):
    """LoRA twin of :func:`_serve_step_sample`."""
    raw, mutated = model.apply(
        {"params": params, "cache": cache}, last_tok[:, None],
        train=False, decode=True, last_only=True, mutable=["cache"],
        cache_positions=lengths.astype(jnp.int32),
        lora_bank=bank, adapter_ids=ids,
    )
    logits = raw[:, -1, :]
    keys = decoding.row_keys(seeds, branches, steps)
    drawn = decoding.sample_rows(logits, temps, top_ks, top_ps,
                                 keys).astype(jnp.int32)
    logprob = jnp.where(active,
                        logprob + decoding.token_logprobs(logits, drawn),
                        logprob)
    nxt = jnp.where(active, drawn, last_tok)
    lengths = jnp.where(active, lengths + 1, lengths)
    steps = jnp.where(active, steps + 1, steps)
    return nxt, lengths, steps, logprob, mutated["cache"]


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(1,))
def _save_blocks(cache, store, block_size, slot, table, n):
    """Copy the first ``n`` full blocks of batch row ``slot`` into the
    physical blocks ``table[:n]`` of the block store (retire-side
    donation). ``table`` is shape-padded to the per-sequence block
    ceiling so slot/table/n are all traced — ONE program and ONE
    dispatch per retire, however many blocks the sequence spans (the
    per-block version made the cache-ON bench dispatch-bound)."""
    def sv(c, s):
        if c.ndim < 2:
            return s
        def body(j, acc):
            blk = jax.lax.dynamic_slice(
                c, (slot, j * block_size) + (0,) * (c.ndim - 2),
                (1, block_size) + c.shape[2:])
            return jax.lax.dynamic_update_slice(
                acc, blk.astype(acc.dtype),
                (table[j], 0) + (0,) * (acc.ndim - 2))
        return jax.lax.fori_loop(0, n, body, s)
    return jax.tree.map(sv, cache, store)


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _restore_blocks(row_cache, store, block_size, table, n):
    """Copy physical blocks ``table[:n]`` of the store into rows
    [0, n * block_size) of a batch-of-one prefill cache
    (admission-side prefix restore; one dispatch per admission). The
    caller guarantees n * block_size <= the row cache's padded length
    (PrefixCache ``max_rows`` caps matches; out-of-range
    dynamic_update_slice starts would silently CLAMP and corrupt
    neighbor rows)."""
    def rs(r, s):
        if r.ndim < 2:
            return r
        def body(j, acc):
            blk = jax.lax.dynamic_slice(
                s, (table[j], 0) + (0,) * (s.ndim - 2),
                (1, block_size) + s.shape[2:])
            return jax.lax.dynamic_update_slice(
                acc, blk.astype(acc.dtype),
                (0, j * block_size) + (0,) * (acc.ndim - 2))
        return jax.lax.fori_loop(0, n, body, r)
    return jax.tree.map(rs, row_cache, store)


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_row(batch_cache, row_cache, slot):
    """Copy a prefilled batch-of-one cache into batch row ``slot``.
    Scalar leaves (the shared cache_index / pos_index counters) are
    untouched — per-row mode never reads them."""
    def ins(b, r):
        if b.ndim == 0:
            return b
        return jax.lax.dynamic_update_slice(
            b, r.astype(b.dtype), (slot,) + (0,) * (b.ndim - 1))
    return jax.tree.map(ins, batch_cache, row_cache)


# init_cache retraces model.init (pure Python, ~100ms even for tiny
# models) and mints each leaf with an eager jnp.zeros: ~85 host
# dispatches for a 17-layer model, per admission, while every slot
# waits. Under jit the retrace runs once per (model, batch, max_len),
# the zeros are broadcasts inside ONE program, and each call is one
# dispatch that returns fresh buffers (they are donated to the prefill
# jit, so a cached array must never be handed out twice). The jit cache
# pins the model the same way the prefill jits do.
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _zero_cache(model, batch, max_len):
    return init_cache(model, batch, max_len)


def _fresh_cache(model, batch: int, max_len: int):
    return _zero_cache(model, batch, max_len)


def _bucket_len(n: int, floor: int = 16) -> int:
    """Round a prompt length up to a power of two (>= ``floor``): the
    prefill/insert jit cache then holds O(log max_seq_len) programs
    instead of one per distinct prompt length."""
    b = floor
    while b < n:
        b *= 2
    return b


class _Slot:
    """Host-side mirror of one batch row (= one decode branch)."""

    __slots__ = ("req", "emitted", "tokens", "depth", "cached",
                 "seq_id", "branch", "step0", "streamed")

    def __init__(self, req: Request, first_token: int, depth: int,
                 cached: int = 0, seq_id: str = "", branch: int = 0):
        self.req = req
        self.tokens = [int(first_token)]
        self.emitted = 1
        self.depth = depth  # cache rows filled (prompt + emitted - 1)
        self.cached = cached  # prompt tokens restored from prefix cache
        # Prism: which pool sequence this row extends (== request_id
        # for branch 0 / unbranched requests), the branch's RNG lane,
        # and the sampling step this leg started at
        self.seq_id = seq_id or req.request_id
        self.branch = branch
        self.step0 = req.decode_step0
        self.streamed = 0  # tokens already pushed to req.stream


class ServingEngine:
    """Continuous-batching engine over one model + params."""

    def __init__(self, model, params, *, max_slots: int = 4,
                 max_seq_len: int = 256, block_size: int = 16,
                 max_queue: int = 64, max_prefills_per_round: int = 2,
                 eos_token: Optional[int] = None, metrics=None,
                 tag: str = "", prefix_cache: bool = True,
                 lora_bank=None, tenant_quotas=None,
                 stream_chunk_tokens: int = 1) -> None:
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.model = model
        self.params = params
        # owner label (fleet replica name): rides every serve_request
        # record so per-replica occupancy survives into the JSONL
        self.tag = tag
        self.max_slots = max_slots
        self.max_seq_len = int(max_seq_len)
        self.eos_token = eos_token
        self.metrics = metrics  # MetricsLogger or None
        # Causeway: give an armed tracer the JSONL sink (no-op when
        # TPUNN_TRACE is unset — zero writes, lint contract)
        trace.attach_metrics(metrics)
        # Abacus: same contract for an armed meter (TPUNN_METER)
        meter.attach_metrics(metrics)
        # Lighthouse: same contract for an armed audit (TPUNN_AUDIT)
        audit.attach_metrics(metrics)
        # fleet replica index (stamped by the fleet supervisor): the
        # chaos flip@replica=K drill keys on it; standalone engines
        # keep 0
        self.replica_index = 0
        # analytic FLOPs per token (utils/flops.py XLA count at batch
        # 1, seq 1): computed lazily on first metered billing, never
        # when the meter is unarmed; 0 = no cost model reachable
        self._flops_per_token: Optional[int] = None
        # per-request LoRA: stacked (n, L, ...) factor bank
        # (nn/lora.py); requests pick an adapter at submit and each
        # batch row applies its own deltas in the shared forward
        self.lora_bank = lora_bank
        pool = KVPool(
            num_blocks=max_slots * (-(-self.max_seq_len // block_size)),
            block_size=block_size,
        )
        self._cache = _fresh_cache(model, max_slots, self.max_seq_len)
        if prefix_cache:
            self.prefix_cache: Optional[PrefixCache] = PrefixCache(
                pool, max_rows=self.max_seq_len, tag=tag)
            # device block store: retired sequences donate their KV
            # blocks here; admissions with a radix match restore from
            # here. Scalar leaves are fresh zeros (NEVER aliased into
            # self._cache — the decode jit donates the cache every
            # round, and an aliased leaf would be invalidated with it).
            self._store = jax.tree.map(
                lambda x: (jnp.zeros_like(x) if x.ndim < 2 else
                           jnp.zeros((pool.num_blocks, block_size)
                                     + x.shape[2:], x.dtype)),
                self._cache)
            # fixed save/restore table width: one compiled program
            # serves every sequence, whatever its block count
            self._blocks_per_seq = -(-self.max_seq_len // block_size)
        else:
            self.prefix_cache = None
            self._store = None
        self.scheduler = Scheduler(
            pool, max_queue=max_queue, max_seq_len=self.max_seq_len,
            max_prefills_per_round=max_prefills_per_round,
            tenant_quotas=tenant_quotas,
            prefix_cache=self.prefix_cache,
        )
        self.scheduler.metrics = metrics
        self._slots: list[Optional[_Slot]] = [None] * max_slots
        self._h_last = np.zeros((max_slots,), np.int32)
        self._h_depth = np.zeros((max_slots,), np.int32)
        self._h_active = np.zeros((max_slots,), bool)
        self._h_adapter = np.zeros((max_slots,), np.int32)
        self._d_last = jnp.asarray(self._h_last)
        self._d_depth = jnp.asarray(self._h_depth)
        self._d_active = jnp.asarray(self._h_active)
        self._d_adapter = jnp.asarray(self._h_adapter)
        # Prism per-row sampling mirrors (serve/decoding.py): synced on
        # admission/retirement like the four above; the decode-sample
        # jit consumes them as traced arrays so any greedy/sampled row
        # mix runs one compiled program. Steps + cumulative logprobs
        # advance ON DEVICE inside the jit.
        self._h_temp = np.zeros((max_slots,), np.float32)
        self._h_topk = np.zeros((max_slots,), np.int32)
        self._h_topp = np.zeros((max_slots,), np.float32)
        self._h_seed = np.zeros((max_slots,), np.int32)
        self._h_branch = np.zeros((max_slots,), np.int32)
        self._h_step = np.zeros((max_slots,), np.int32)
        self._d_temp = jnp.asarray(self._h_temp)
        self._d_topk = jnp.asarray(self._h_topk)
        self._d_topp = jnp.asarray(self._h_topp)
        self._d_seed = jnp.asarray(self._h_seed)
        self._d_branch = jnp.asarray(self._h_branch)
        self._d_step = jnp.asarray(self._h_step)
        self._d_logprob = jnp.zeros((max_slots,), jnp.float32)
        # prefill-sampled first-token logprobs, applied to _d_logprob
        # at the next sync (slot index -> value)
        self._pending_logprob: dict[int, float] = {}
        self._n_sampled = 0  # active slots needing the sampled jit
        # best-of-n bookkeeping: request_id -> {branch: (tokens, logprob)}
        self._branch_done: dict[str, dict[int, tuple]] = {}
        # incremental streaming: tokens per chunk (1 = every token is
        # a chunk). Chunking never changes the retired fingerprint —
        # the Lighthouse fold runs over the full token list at retire.
        self.stream_chunk_tokens = max(int(stream_chunk_tokens), 1)
        # bench/report feed: per-round wall seconds + finished requests
        self.round_seconds: list[float] = []
        self.completed: list[dict] = []
        self._occ_sum = 0  # sum of per-round active-slot counts
        reg = obs.get_registry()
        self._h_ttft = reg.histogram(
            "serve_ttft_seconds", "submit -> first token",
            buckets=_TTFT_BUCKETS)
        # per-tenant twin of serve_ttft_seconds — the base histogram
        # stays UNLABELED (its series is the global SLO feed; labeling
        # it would break every existing snapshot() caller)
        self._h_ttft_tenant = reg.histogram(
            "serve_tenant_ttft_seconds",
            "submit -> first token, per tenant",
            labels=("tenant",), buckets=_TTFT_BUCKETS)
        self._h_tok = reg.histogram(
            "serve_token_latency_seconds", "decode round wall time "
            "(= per-token latency of every active stream)",
            buckets=_TOKEN_BUCKETS)
        self._g_occ = reg.gauge(
            "serve_batch_occupancy", "active decode slots")
        self._c_tokens = reg.counter(
            "serve_tokens_total", "tokens emitted by the engine")
        self._c_stream_chunks = reg.counter(
            "serve_stream_chunks_total",
            "token chunks pushed to streaming clients")

    # -- client surface ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, **kw) -> Request:
        adapter = int(kw.get("adapter", 0))
        if self.lora_bank is not None:
            n = num_adapters(self.lora_bank)
            if not 0 <= adapter < n:
                raise ValueError(
                    f"adapter {adapter} out of range for a LoRA bank "
                    f"of {n} adapters")
        elif adapter != 0:
            raise ValueError(
                f"adapter {adapter} requested but the engine has no "
                f"LoRA bank (pass lora_bank= to ServingEngine)")
        spec = kw.get("decode")
        if spec is not None \
                and getattr(spec, "branches", 1) > self.max_slots:
            raise ValueError(
                f"best_of={getattr(spec, 'branches', 1)} branches can "
                f"never fit a {self.max_slots}-slot engine")
        return self.scheduler.submit(prompt, max_new_tokens, **kw)

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def has_work(self) -> bool:
        return self.active_slots > 0 or self.scheduler.queue_depth > 0

    # -- engine loop pieces (one driving thread) ---------------------------

    def step(self) -> bool:
        """One scheduler round: admit + prefill into free slots, one
        batched decode round, retire finished rows. Returns False when
        there was nothing to do (caller may sleep/park)."""
        sched = self.scheduler
        sched.round += 1
        with obs.span("serve/round", round=sched.round) as rnd:
            # chaos tenant_flood: synthetic burst traffic lands through the
            # REAL submit path (quota checks, DRR queues, reject counters)
            for tenant, owed in chaos.on_tenant_flood():
                for _ in range(owed):
                    self.submit(np.asarray([3, 5, 7], np.int32), 2,
                                tenant=tenant)
            changed = self._admit()
            if self.active_slots == 0:
                self._g_occ.set(0)
                if changed:
                    self._sync_slots()
                rnd.set(occ=0)
                return changed
            with obs.span("serve/decode"):
                host_tok, dt = self._decode_round()
            with obs.span("serve/round_host") as host_span:
                self.round_seconds.append(dt)
                self._h_tok.observe(dt)
                occ = self.active_slots
                self._g_occ.set(occ)
                self._c_tokens.inc(occ)
                self._occ_sum += occ
                flight.record("serve", "decode_round", step=sched.round,
                              note=f"occ={occ}/{self.max_slots}")
                # watchtower feed (token-latency SLO + queue/KV pressure):
                # here, NOT in _decode_round — its hot-loop lint bans extras
                watchtower.on_serve_round(
                    sched.round, dt, queue_depth=sched.queue_depth,
                    queue_max=sched.max_queue,
                    kv_free=sched.pool.free_blocks,
                    kv_total=sched.pool.num_blocks)
                # helm feed (instantaneous queue/KV between control ticks);
                # inert one-comparison no-op unless TPUNN_AUTOSCALE armed it
                autoscale.on_serve_round(
                    sched.round, dt, queue_depth=sched.queue_depth,
                    queue_max=sched.max_queue,
                    kv_free=sched.pool.free_blocks,
                    kv_total=sched.pool.num_blocks)
                # xray capture clock (serving-side): rounds advance an active
                # capture window / interval trigger, same placement rule
                xray.on_serve_round(sched.round)
                # Abacus decode billing: one token per active slot this round,
                # split by tenant — here, NOT in _decode_round (hot-loop lint).
                # enabled() gate so the slot scan + FLOPs lookup never run on
                # an unarmed process (the armed-vs-unset A/B contract)
                if meter.enabled():
                    # Lighthouse shadow/probe legs are audit duplicates, not
                    # customer traffic — their decode rounds are never billed
                    meter.on_decode_round(
                        [s.req.tenant for s in self._slots if s is not None
                         and s.req.tenant != audit.SHADOW_TENANT],
                        self.flops_per_token())
                retired = self._collect(host_tok)
                if retired:
                    self._sync_slots()
                host_span.set(retired=retired)
            rnd.set(occ=occ - retired)
            return True

    def run_until_idle(self) -> None:
        """Drive rounds until queue and batch are both empty."""
        while self.has_work:
            self.step()

    def drain(self) -> int:
        """Graceful shutdown: reject everything queued, finish every
        in-flight sequence, leave the batch empty. Returns the number
        of requests that were still queued (now rejected)."""
        rejected = self.scheduler.drain()
        while self.active_slots > 0:
            self.step()
        flight.record("serve", "drained",
                      note=f"rejected_queued={rejected}")
        return rejected

    # -- internals ---------------------------------------------------------

    def _admit(self) -> bool:
        """Pull scheduler admissions into free slots and prefill them."""
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return False
        admitted = self.scheduler.next_admissions(len(free))
        if not admitted:
            return False
        with obs.span("serve/admit", n=len(admitted)):
            for req in admitted:
                # a branched request claims one row per branch (the
                # scheduler already counted them against free_slots)
                slots = [free.pop(0) for _ in range(req.branches)]
                self._prefill_into(slots, req)
            # a budget-1 (or instant-eos) request retires in the same
            # pass
            self._retire_finished()
            self._sync_slots()
        return True

    def _prefill_into(self, slots: list, req: Request) -> None:
        """Prefill ONE request into ``len(slots)`` batch rows. The
        prompt forward runs once; branched requests fan the resulting
        row cache into every branch row (``_insert_row`` donates only
        the batch cache, so one prefilled row inserts n times) and
        draw each branch's first token from the same prompt logits
        under its own RNG lane."""
        L = len(req.prompt)
        spec = req.decode
        sampled = spec is not None and spec.sampled
        match = req.prefix_match
        m = match.tokens if match is not None else 0
        bs = self.scheduler.pool.block_size
        suffix = np.asarray(req.prompt[m:], np.int32)
        T = len(suffix)  # >= 1: PrefixCache caps matches at L - 1
        t_pad = min(_bucket_len(T), self.max_seq_len - m)
        # row-cache length must hold BOTH the restored blocks and the
        # suffix writes: a dynamic_update_slice whose start exceeds the
        # buffer silently clamps (corrupting neighbor rows), so pad is
        # sized to max(restored top, m + suffix pad), never less
        restore_top = len(match.restore_blocks) * bs \
            if match is not None else 0
        pad = min(_bucket_len(max(m + t_pad, restore_top)),
                  self.max_seq_len)
        with obs.span("serve/prefill_into", request=req.request_id,
                      tokens=T, padded=t_pad, cached=m, row_len=pad):
            tokens = np.zeros((1, t_pad), np.int32)
            tokens[0, :T] = suffix  # left-ALIGNED (pad tail is masked)
            with obs.span("serve/fresh_cache"):
                row_cache = _fresh_cache(self.model, 1, pad)
            if m > 0:
                nb = len(match.restore_blocks)
                table = np.zeros((self._blocks_per_seq,), np.int32)
                table[:nb] = match.restore_blocks
                t_restore = time.monotonic()
                with obs.span("serve/restore", blocks=nb):
                    row_cache = _restore_blocks(
                        row_cache, self._store, bs, table, np.int32(nb))
                trace.on_segment(req.trace, "restore", t_restore,
                                 time.monotonic(), blocks=nb, cached=m)
            logps: Optional[list] = None
            with obs.span("serve/prefill", request=req.request_id,
                          prompt_len=L, cached=m):
                if not sampled:
                    # inert-defaults contract: this arm is the EXACT
                    # pre-Prism call (test_quality pins its shape), so
                    # greedy requests stay byte-identical
                    if self.lora_bank is None:
                        tok0, row_cache = _serve_prefill(
                            self.model, self.params, row_cache,
                            jnp.asarray(tokens), jnp.asarray([T], jnp.int32),
                            jnp.asarray([m], jnp.int32))
                    else:
                        tok0, row_cache = _serve_prefill_lora(
                            self.model, self.params, row_cache,
                            jnp.asarray(tokens), jnp.asarray([T], jnp.int32),
                            jnp.asarray([m], jnp.int32), self.lora_bank,
                            jnp.asarray([req.adapter], jnp.int32))
                    firsts = [int(np.asarray(tok0)[0])]
                else:
                    if self.lora_bank is None:
                        next_logits, row_cache = _serve_prefill_logits(
                            self.model, self.params, row_cache,
                            jnp.asarray(tokens), jnp.asarray([T], jnp.int32),
                            jnp.asarray([m], jnp.int32))
                    else:
                        next_logits, row_cache = _serve_prefill_logits_lora(
                            self.model, self.params, row_cache,
                            jnp.asarray(tokens), jnp.asarray([T], jnp.int32),
                            jnp.asarray([m], jnp.int32), self.lora_bank,
                            jnp.asarray([req.adapter], jnp.int32))
                    toks, lps = _sample_first(
                        next_logits, len(slots),
                        np.float32(spec.temperature), np.int32(spec.top_k),
                        np.float32(spec.top_p), np.int32(spec.seed),
                        np.int32(req.decode_step0))
                    firsts = [int(t) for t in np.asarray(toks)]
                    logps = [float(x) for x in np.asarray(lps)]
            if match is not None:
                # restored rows are copied out; the COW tail pin can drop
                self.prefix_cache.finish_restore(match)
                req.prefix_match = None
            now = time.monotonic()
            req.t_first_token = now
            # TTFT is charged from the logical request's ORIGINAL arrival
            # (t_origin: set by the fleet on resubmitted legs), and only
            # when THIS leg delivers the first token — a disagg decode leg
            # or a post-first-token failover re-admission arrives with
            # t_first_origin already set and must not observe again (the
            # capacity sim's accounting, now pinned for the live fleet too)
            if req.t_first_origin == 0.0:
                ttft = now - (req.t_origin or req.t_submit)
                self._h_ttft.observe(ttft)
                self._h_ttft_tenant.observe(ttft, tenant=req.tenant)
            sids = branch_seq_ids(req)
            with obs.span("serve/insert_row", rows=len(slots)):
                for k, slot in enumerate(slots):
                    self._cache = _insert_row(self._cache, row_cache, slot)
                    s = _Slot(req, firsts[k], depth=L, cached=m,
                              seq_id=sids[k], branch=k)
                    self._slots[slot] = s
                    self._h_last[slot] = firsts[k]
                    self._h_depth[slot] = L
                    self._h_active[slot] = True
                    self._h_adapter[slot] = req.adapter
                    # reset the sampling mirrors: slots are reused, and a
                    # greedy row landing on a retired sampled row must read
                    # temperature 0 (the jit's per-row greedy branch)
                    self._h_temp[slot] = spec.temperature if sampled else 0.0
                    self._h_topk[slot] = spec.top_k if sampled else 0
                    self._h_topp[slot] = spec.top_p if sampled else 0.0
                    self._h_seed[slot] = spec.seed if sampled else 0
                    self._h_branch[slot] = k
                    self._pending_logprob[slot] = logps[k] if sampled else 0.0
                    self._c_tokens.inc()  # the prefill-produced first token
                    flight.record("serve", "admit", step=self.scheduler.round,
                                  note=f"{sids[k]} slot={slot} L={L} "
                                       f"cached={m}")
                    if k == 0:
                        # first chunk = the client-visible TTFT event (no-op
                        # for non-streaming requests)
                        self._emit_chunk(s)
            # Abacus prefill billing: the suffix actually computed, plus
            # the cached-prefix FLOPs the restore SKIPPED as a credit
            # (audit shadow/probe legs are never billed)
            if meter.enabled() and req.tenant != audit.SHADOW_TENANT:
                meter.on_prefill(req.request_id, req.tenant,
                                 new_tokens=T, cached_tokens=m,
                                 flops_per_token=self.flops_per_token())

    def _decode_round(self):
        """THE hot loop body (see module docstring for the lint
        contract: no host->device transfers, no jnp/jax array
        construction — device state stays resident; one (slots,)
        device->host fetch)."""
        t0 = time.monotonic()
        # chaos slow@/crash@/preempt@ key on the decode round the way
        # they key on the training step; inside the timed window so an
        # injected slow round shows up in the latency histograms
        # exactly like a real one
        chaos.on_step(self.scheduler.round)
        if self._n_sampled == 0:
            # inert-defaults contract: an all-greedy batch runs the
            # EXACT pre-Prism jits (test_quality pins the call shape),
            # so default requests stay byte-identical
            if self.lora_bank is None:
                nxt, depth, self._cache = _serve_step(
                    self.model, self.params, self._cache, self._d_last,
                    self._d_depth, self._d_active)
            else:
                nxt, depth, self._cache = _serve_step_lora(
                    self.model, self.params, self._cache, self._d_last,
                    self._d_depth, self._d_active, self.lora_bank,
                    self._d_adapter)
        elif self.lora_bank is None:
            nxt, depth, self._d_step, self._d_logprob, self._cache = \
                _serve_step_sample(
                    self.model, self.params, self._cache, self._d_last,
                    self._d_depth, self._d_active, self._d_temp,
                    self._d_topk, self._d_topp, self._d_seed,
                    self._d_branch, self._d_step, self._d_logprob)
        else:
            nxt, depth, self._d_step, self._d_logprob, self._cache = \
                _serve_step_sample_lora(
                    self.model, self.params, self._cache, self._d_last,
                    self._d_depth, self._d_active, self.lora_bank,
                    self._d_adapter, self._d_temp, self._d_topk,
                    self._d_topp, self._d_seed, self._d_branch,
                    self._d_step, self._d_logprob)
        self._d_last, self._d_depth = nxt, depth
        host_tok = np.asarray(nxt)
        return host_tok, time.monotonic() - t0

    def _collect(self, host_tok: np.ndarray) -> int:
        """Fold one round's tokens into the host slot mirrors and
        retire rows that hit eos or budget. Returns retired count."""
        # chaos flip@replica=K: perturb ONE fetched token (first active
        # slot) this round — a silent corruption: the wrong id flows
        # into the slot mirror, the JSONL record, and the fingerprint
        # chain exactly as flaky HBM would ship it. Host-side, outside
        # _decode_round (its hot-loop lint bans extras).
        flip = chaos.on_flip_token(self.replica_index,
                                   self.scheduler.round)
        flipped = False
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            tok = int(host_tok[i])
            if flip:
                flip = False
                flipped = True
                tok = tok - 1 if tok > 0 else tok + 1
            s.tokens.append(tok)
            s.emitted += 1
            s.depth += 1
            self._h_last[i] = tok
            self._h_depth[i] = s.depth
            self.scheduler.pool.extend(s.seq_id, s.depth)
            if s.req.stream is not None and \
                    len(s.tokens) - s.streamed >= self.stream_chunk_tokens:
                self._emit_chunk(s)
        retired = self._retire_finished()
        if flipped:
            # push the corrupted last-token mirror to device (mirrors
            # are all current here) so the flip PROPAGATES: subsequent
            # tokens condition on the wrong id, exactly like real rot
            self._sync_slots()
        return retired

    def _done(self, s: _Slot) -> bool:
        if s.emitted >= s.req.max_new_tokens:
            return True
        return self.eos_token is not None and \
            s.tokens[-1] == self.eos_token

    def _retire_finished(self) -> int:
        retired = 0
        with obs.span("serve/retire") as sp:
            for i, s in enumerate(self._slots):
                if s is None or not self._done(s):
                    continue
                self._slots[i] = None
                self._h_active[i] = False
                retired += 1
                req = s.req
                if req.branches > 1:
                    self._retire_branch(i, s)
                    continue
                if self.prefix_cache is not None:
                    # donate BEFORE retire: release() indexes the physical
                    # blocks into the radix, so their bytes must already be
                    # in the store when another admission can match them
                    self._donate_blocks(i, s)
                # final flush BEFORE retire: the closing chunk must be in
                # the stream when done.set() wakes the client
                self._emit_chunk(s, final=True)
                self.scheduler.retire(req, np.asarray(s.tokens, np.int32))
                flight.record("serve", "retire", step=self.scheduler.round,
                              note=f"{req.request_id} tokens={s.emitted}")
                self._finish_record(req, s)
            sp.set(n=retired)
        return retired

    def _retire_branch(self, slot: int, s: _Slot) -> None:
        """Retire ONE branch of a best-of-n request: bank its tokens +
        device-accumulated logprob, free its KV tail (each branch
        retires at its OWN eos/budget — a short branch's blocks return
        to the pool while its siblings decode on). The request itself
        retires when its last branch lands: rank by cumulative
        logprob, hand the client the top ``n``."""
        req = s.req
        # outside the hot loop (retirement path), so the fetch is
        # legal. A budget-1 branch retires in the same _admit pass
        # that prefilled it — before _sync_slots merged its first
        # token's logprob to device — so the pending value wins.
        lp = self._pending_logprob.pop(slot, None)
        if lp is None:
            lp = float(np.asarray(self._d_logprob)[slot])
        self.scheduler.release_branch(req, s.seq_id)
        done = self._branch_done.setdefault(req.request_id, {})
        done[s.branch] = (list(s.tokens), lp)
        flight.record("serve", "retire_branch",
                      step=self.scheduler.round,
                      note=f"{s.seq_id} tokens={s.emitted} "
                           f"logprob={lp:.4f}")
        if len(done) < req.branches:
            return
        del self._branch_done[req.request_id]
        # highest cumulative logprob wins; branch index breaks ties
        # deterministically
        order = sorted(done.items(), key=lambda kv: (-kv[1][1], kv[0]))
        n_best = [dict(branch=k, tokens=list(t), logprob=lp)
                  for k, (t, lp) in order][:req.decode.n]
        win_tokens, win_lp = done[order[0][0]]
        # the winning branch's view rides the JSONL record: reuse the
        # last slot mirror as the record carrier
        s.tokens = win_tokens
        s.emitted = len(win_tokens)
        self.scheduler.finish_branches(
            req, np.asarray(win_tokens, np.int32), n_best, win_lp)
        flight.record("serve", "retire", step=self.scheduler.round,
                      note=f"{req.request_id} tokens={s.emitted} "
                           f"branches={req.branches}")
        self._finish_record(req, s)

    def _emit_chunk(self, s: _Slot, final: bool = False) -> None:
        """THE streaming funnel: every token chunk a client sees flows
        through this one ``TokenStream._feed`` call site (lint-pinned),
        so chunk accounting (counter, flight, JSONL) can never drift
        from what was actually delivered. No-op for non-streaming
        requests."""
        stream = s.req.stream
        if stream is None:
            return
        chunk = s.tokens[s.streamed:]
        if chunk:
            first = s.streamed == 0
            s.streamed = len(s.tokens)
            stream._feed(chunk)
            self._c_stream_chunks.inc()
            flight.record("serve", "stream_chunk",
                          step=self.scheduler.round,
                          note=f"{s.req.request_id} n={len(chunk)}"
                               f"{' first' if first else ''}"
                               f"{' final' if final else ''}")
            if self.metrics is not None:
                self.metrics.emit(
                    "serve_stream_chunk", request_id=s.req.request_id,
                    tokens=len(chunk), first=first, final=final)
        if final:
            stream.close()

    def _donate_blocks(self, slot: int, s: _Slot) -> None:
        """Copy the retiring slot's full KV blocks into the device
        store. Count matches what ``PrefixCache.release`` will index:
        ``depth // block_size`` full blocks (depth = prompt + emitted
        - 1 = exactly the rows whose tokens the scheduler hands to
        release). Re-saving a block the radix already owns writes
        bit-identical bytes — harmless."""
        pool = self.scheduler.pool
        bs = pool.block_size
        table = pool.block_table(s.req.request_id)
        nb = min(s.depth // bs, len(table))
        if nb == 0:
            return
        padded = np.zeros((self._blocks_per_seq,), np.int32)
        padded[:nb] = table[:nb]
        self._store = _save_blocks(
            self._cache, self._store, bs,
            np.int32(slot), padded, np.int32(nb))

    def export_blocks(self, table):
        """Host-side copy of physical store blocks ``table`` (leading
        axis = position in the streamed chain) — the transfer SOURCE of
        KV block streaming (:mod:`serve.disagg`). Reads the device
        block store the retire path's ``_save_blocks`` maintains; the
        caller pins the blocks in the pool across the export window so
        eviction cannot recycle them before the peer's write lands.
        Non-block leaves (ndim < 2 scalars) ship as empty placeholders
        so the pytree structure round-trips."""
        idx = jnp.asarray(np.asarray(table, np.int32))
        return jax.tree.map(
            lambda s: np.asarray(s[idx]) if s.ndim >= 2
            else np.zeros((), s.dtype), self._store)

    def ingest_blocks(self, tokens, host_blocks, adapter: int = 0) -> int:
        """Transfer SINK of KV block streaming: index ``tokens``'s full
        blocks in this engine's prefix cache (:meth:`PrefixCache.
        ingest` adopts cached-ring blocks from the free list) and
        scatter the streamed ``host_blocks`` rows into the device store
        at the adopted ids. Already-resident blocks dedup by digest and
        are not rewritten. Returns blocks written; 0 when this engine
        has no prefix cache or the pool had no headroom to adopt."""
        if self.prefix_cache is None or self._store is None:
            return 0
        plan = self.prefix_cache.ingest(tokens, adapter)
        if not plan:
            return 0
        src = jnp.asarray(np.asarray([j for j, _ in plan], np.int32))
        dst = jnp.asarray(np.asarray([p for _, p in plan], np.int32))
        self._store = jax.tree.map(
            lambda d, b: d.at[dst].set(jnp.asarray(b)[src])
            if d.ndim >= 2 else d, self._store, host_blocks)
        return len(plan)

    def _finish_record(self, req: Request, s: _Slot) -> None:
        # TTFT from the logical request's original arrival: for a
        # resubmitted leg, t_origin is the FIRST submit and
        # t_first_origin (if set) the first token an earlier leg
        # already delivered — the JSONL must agree with the fleet
        # ticket and the capacity sim, not restart the clock per leg
        origin = req.t_origin or req.t_submit
        t_first = req.t_first_origin or req.t_first_token
        ttft = t_first - origin
        total = req.t_done - req.t_submit
        decode = req.t_done - req.t_first_token
        per_tok = decode / max(s.emitted - 1, 1)
        # per-request waterfall: the request_id's timeline through
        # admission -> queue -> prefill -> decode -> retire, from the
        # scheduler's lifecycle timestamps + round bookkeeping. Rides
        # the serve_request JSONL record, the retroactive trace span's
        # phase children, and any watchtower alert that names this
        # request.
        waterfall = dict(
            queued_s=round(max(req.t_admit - req.t_submit, 0.0), 6),
            prefill_s=round(max(req.t_first_token - req.t_admit, 0.0),
                            6),
            decode_s=round(max(decode, 0.0), 6),
            round_submitted=req.round_submitted,
            round_admitted=req.round_admitted,
            round_done=req.round_done,
        )
        rec = dict(
            request_id=req.request_id, prompt_len=len(req.prompt),
            new_tokens=s.emitted, ttft_s=ttft, total_s=total,
            per_token_s=per_tok,
            rounds_waited=req.round_admitted - req.round_submitted,
            kv_util=self.scheduler.pool.utilization(),
            waterfall=waterfall,
            tenant=req.tenant, adapter=req.adapter,
            cached_tokens=s.cached,
        )
        if self.tag:
            rec["replica"] = self.tag
        # Prism keys: absent for default requests (key-absent wire
        # discipline — a greedy, non-streaming run's JSONL is
        # byte-identical to a pre-Prism build)
        if req.decode is not None:
            rec["decode"] = req.decode.to_wire()
        if req.n_best is not None:
            rec["branches"] = req.branches
            rec["logprob"] = round(req.logprob, 6)
        if req.stream is not None:
            rec["stream_chunks"] = req.stream.chunks
        if req.trace is not None:
            # the record names its trace (watchtower pages attach it;
            # key absent when untraced, so replayed streams from an
            # unarmed run stay byte-identical)
            rec["trace"] = req.trace.trace_id
        # Lighthouse fingerprint: THE one engine call site that folds a
        # request's emitted tokens onto its chain seed (lint-pinned).
        # None unarmed — the fp key stays absent and the record stream
        # is byte-identical to a pre-audit run.
        fp = audit.on_retire(req.request_id, s.tokens,
                             seed=req.fp_seed, replica=self.tag)
        if fp is not None:
            rec["fp"] = fp
        self.completed.append(rec)
        if self.metrics is not None:
            self.metrics.emit("serve_request", **rec)
        watchtower.on_serve_request(rec)
        # Abacus lifecycle charges (queue/decode wall time, tokens,
        # the per-request JSONL record, the cost-anomaly feed). Audit
        # shadow/probe legs are duplicates, never billed.
        if meter.enabled() and req.tenant != audit.SHADOW_TENANT:
            meter.on_request_done(rec, self.flops_per_token())
        # Causeway segments, retroactive from the scheduler's
        # lifecycle timestamps — the decode hot loop stays untouched
        # (its lint bans extras); resubmit legs ride the ctx the fleet
        # minted/linked
        trace.on_segment(req.trace, "queued", req.t_submit,
                         req.t_admit, request_id=req.request_id,
                         replica=self.tag)
        trace.on_segment(req.trace, "prefill", req.t_admit,
                         req.t_first_token, request_id=req.request_id,
                         replica=self.tag, cached=s.cached,
                         prompt_len=len(req.prompt))
        seg_kw = dict(request_id=req.request_id, replica=self.tag,
                      tokens=s.emitted)
        if fp is not None:
            # the decode span carries the leg fingerprint so a trace
            # waterfall can show WHERE a chain diverged across legs
            seg_kw["fp"] = fp
        trace.on_segment(req.trace, "decode", req.t_first_token,
                         req.t_done, **seg_kw)
        tracer = obs.current_recorder()
        if tracer is not None:
            # retroactive per-request span: duration is only known now
            end_us = tracer._now_us()
            t0_us = end_us - total * 1e6
            tracer.add_event(f"serve/{req.request_id}",
                             t0_us, total * 1e6,
                             cat="serve", args=dict(
                                 prompt_len=len(req.prompt),
                                 new_tokens=s.emitted,
                                 ttft_ms=ttft * 1e3))
            off_us = 0.0
            for phase in ("queued", "prefill", "decode"):
                dur_us = waterfall[f"{phase}_s"] * 1e6
                if dur_us > 0:
                    tracer.add_event(
                        f"serve/{req.request_id}/{phase}",
                        t0_us + off_us, dur_us, cat="serve")
                off_us += dur_us

    def _sync_slots(self) -> None:
        """Push the host slot mirrors to device (admission/retirement
        path only — never per round)."""
        self._d_last = jnp.asarray(self._h_last)
        self._d_depth = jnp.asarray(self._h_depth)
        self._d_active = jnp.asarray(self._h_active)
        self._d_adapter = jnp.asarray(self._h_adapter)
        # Prism mirrors: recompute which rows need the sampled jit and
        # each row's RNG step (step0 + emitted — recomputable host-side
        # by design, so a flip-drill mid-round resync cannot skew the
        # device counter)
        self._n_sampled = 0
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            self._h_step[i] = s.step0 + s.emitted
            if s.req.decode is not None and s.req.decode.sampled:
                self._n_sampled += 1
        if self._n_sampled or self._pending_logprob:
            # logprobs accumulate ON DEVICE: pull, overlay the prefill
            # first-token values, push back (retirement path only)
            h_logprob = np.asarray(self._d_logprob).copy()
            for slot, v in self._pending_logprob.items():
                h_logprob[slot] = v
            self._pending_logprob.clear()
            self._d_logprob = jnp.asarray(h_logprob)
        self._d_temp = jnp.asarray(self._h_temp)
        self._d_topk = jnp.asarray(self._h_topk)
        self._d_topp = jnp.asarray(self._h_topp)
        self._d_seed = jnp.asarray(self._h_seed)
        self._d_branch = jnp.asarray(self._h_branch)
        self._d_step = jnp.asarray(self._h_step)

    def flops_per_token(self) -> int:
        """Analytic forward FLOPs of ONE token through this model
        (:func:`utils.flops.fwd_flops` at batch 1, seq 1) — the unit
        every Abacus billing multiplies. Integer (exact per-tenant
        sums), computed once per engine. On a CPU backend a failed
        count bills 0 FLOPs (tokens/residency/wire still meter); on the
        chip it raises. Only metered paths call this, so an unarmed
        process never pays the lowering."""
        if self._flops_per_token is None:
            from pytorch_distributed_nn_tpu.utils.flops import fwd_flops

            try:
                self._flops_per_token = int(round(
                    fwd_flops(self.model, (1, 1), jnp.int32)))
            except RuntimeError:
                if jax.default_backend() == "tpu":
                    raise
                self._flops_per_token = 0
        return self._flops_per_token

    def summary(self) -> dict:
        """Engine-lifetime aggregates (bench + serve_summary JSONL)."""
        # flush per-tenant meter_ledger JSONL records (inert no-op
        # unless TPUNN_METER armed): a finished run's stream carries
        # the final ledgers for obs_cost/obs_report
        meter.on_serve_summary()
        rounds = len(self.round_seconds)
        occ = self._occ_sum / max(rounds * self.max_slots, 1)
        out = dict(
            rounds=rounds,
            requests_done=len(self.completed),
            tokens_out=int(sum(r["new_tokens"] for r in self.completed)),
            occupancy=occ,
            kv_util=self.scheduler.pool.utilization(),
            queue_depth=self.scheduler.queue_depth,
        )
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
        return out
