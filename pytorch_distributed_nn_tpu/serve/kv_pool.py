"""Paged KV-cache pool: block allocator + per-sequence page table.

Serving memory is KV-cache memory. A naive engine sizes every sequence
for the worst case (max prompt + max generation) and admits
``HBM / worst_case`` sequences; vLLM's observation is that paging the
cache in fixed-size blocks and admitting against the *pool* lets the
scheduler pack many more sequences because most finish early or short.
This module is that accounting layer for the continuous-batching engine
(:mod:`serve.engine`).

Design (and its honest scope):

- the pool is ``num_blocks`` blocks of ``block_size`` token slots; a
  sequence admitted with prompt length L and generation budget n
  **reserves** ``ceil((L + n) / block_size)`` blocks up front and holds
  them until it is freed. Reservation-at-admission means a running
  sequence can NEVER hit an out-of-blocks wall mid-decode —
  :meth:`KVPool.extend` only moves the sequence's high-water mark
  inside its own reservation, so there is no eviction/swap path to get
  wrong (the classic continuous-batching deadlock: every running
  sequence needs one more block and none can finish);
- each sequence's reservation is tracked as an explicit **block table**
  (logical block -> physical block id), the structure a true paged
  attention kernel would consume. The current engine stores K/V rows
  slot-contiguously in a dense ``(slots, S_max)`` cache (XLA-friendly;
  no gather in the attention hot loop on CPU/TPU without a custom
  kernel), so the table governs *admission and accounting*, not the
  physical layout — the honest reading is "paged admission control over
  a dense cache". The allocator API is the kernel-ready one so a Pallas
  paged-attention kernel can slot in without scheduler changes;
- **prefix sharing** (the Mosaic tentpole): a block has three lives.
  *Live-exclusive* — inside exactly one sequence's table (the classic
  case above). *Live-shared* — inside several tables at once via
  ``reserve(shared=...)``, refcounted; the blocks return to circulation
  only when the last sharer frees them. *Cached* — refcount-zero blocks
  a retiring sequence donated with ``free(retain=...)`` park in an LRU
  ring instead of the free list, so :mod:`serve.prefix_cache` can hand
  them to a later request that shares the prefix. The free list stays
  the backpressure truth (``free_blocks`` never counts cached blocks);
  the prefix cache sheds cached blocks with :meth:`release_cached` when
  a cold reservation needs them back, honoring :meth:`pin` (a
  copy-on-write tail mid-restore must not vanish under the engine);
- utilization lands in the metric registry as gauges
  (``serve_kv_blocks_total`` / ``serve_kv_blocks_reserved`` /
  ``serve_kv_blocks_used`` / ``serve_kv_blocks_cached``) every time the
  pool changes, so dashboards and :mod:`scripts.obs_report` see cache
  pressure without polling.

Thread-safety: one lock around every mutation — the scheduler thread
and submitting client threads both touch the pool.
"""

from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Iterable

from pytorch_distributed_nn_tpu.obs import meter
from pytorch_distributed_nn_tpu.obs.registry import get_registry


class KVPool:
    """Fixed-size block pool with per-sequence reservations."""

    def __init__(self, num_blocks: int, block_size: int) -> None:
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._lock = threading.Lock()
        self._free: list[int] = list(range(num_blocks - 1, -1, -1))
        # seq_id -> block table (physical block ids, allocation order)
        self._tables: dict[str, list[int]] = {}
        # seq_id -> tokens actually written (high-water mark)
        self._used_tokens: dict[str, int] = {}
        # phys block -> live sharer count (only blocks entered via
        # reserve(shared=); exclusively-owned blocks have no entry)
        self._ref: dict[int, int] = {}
        # refcount-0 donated blocks, LRU order (oldest first)
        self._cached: OrderedDict[int, None] = OrderedDict()
        # cached blocks the prefix cache is mid-restore on: eviction-proof
        self._pinned: set[int] = set()
        reg = get_registry()
        self._g_total = reg.gauge(
            "serve_kv_blocks_total", "KV pool size in blocks")
        self._g_reserved = reg.gauge(
            "serve_kv_blocks_reserved", "KV blocks reserved by admitted "
            "sequences")
        self._g_used = reg.gauge(
            "serve_kv_blocks_used", "KV blocks backing written tokens")
        self._g_cached = reg.gauge(
            "serve_kv_blocks_cached", "refcount-0 prefix blocks parked "
            "in the cached-LRU ring")
        self._c_branches = reg.counter(
            "serve_branches_total", "n-best decode branches forked off "
            "a primary reservation (COW prompt sharing)")
        self._g_total.set(num_blocks)
        self._publish_locked()

    # -- accounting helpers ------------------------------------------------

    def blocks_for(self, tokens: int) -> int:
        """ceil(tokens / block_size) — the reservation for a sequence
        whose cache will hold at most ``tokens`` rows."""
        return -(-max(int(tokens), 0) // self.block_size)

    def _publish_locked(self) -> None:
        reserved = self.num_blocks - len(self._free) - len(self._cached)
        used = sum(self.blocks_for(t) for t in self._used_tokens.values())
        self._g_reserved.set(reserved)
        self._g_used.set(used)
        self._g_cached.set(len(self._cached))

    # -- allocator ---------------------------------------------------------

    def can_reserve(self, tokens: int) -> bool:
        with self._lock:
            return self.blocks_for(tokens) <= len(self._free)

    def reserve(self, seq_id: str, tokens: int,
                shared: Iterable[int] = ()) -> bool:
        """Reserve blocks for a sequence's worst-case ``tokens`` rows.
        False (and no state change) when the pool can't cover it — the
        scheduler's backpressure signal. A second reserve for a live
        ``seq_id`` is a programming error and raises.

        ``shared`` prepends already-materialized prefix blocks (from
        the cached ring or another live sharer's table) to this
        sequence's block table instead of allocating fresh ones: a
        cached block leaves the ring and becomes live with refcount 1;
        an already-live shared block just gains a sharer. Only the
        remainder ``blocks_for(tokens) - len(shared)`` comes off the
        free list, which is the whole prefix-cache win."""
        shared = list(shared)
        n = self.blocks_for(tokens)
        n_fresh = n - len(shared)
        if n_fresh < 0:
            raise ValueError(
                f"sequence {seq_id!r}: {len(shared)} shared blocks exceed "
                f"the {n}-block reservation for {tokens} tokens")
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id!r} already holds a "
                                 f"reservation")
            for b in shared:
                if b not in self._cached and b not in self._ref \
                        and not any(b in t for t in self._tables.values()):
                    raise ValueError(
                        f"shared block {b} is neither cached nor live — "
                        f"the prefix index is stale")
            if n_fresh > len(self._free):
                return False
            for b in shared:
                if b in self._cached:
                    del self._cached[b]
                    self._ref[b] = 1
                else:
                    self._ref[b] = self._ref.get(b, 1) + 1
            table = self._tables[seq_id] = shared + [
                self._free.pop() for _ in range(n_fresh)]
            self._used_tokens[seq_id] = 0
            self._publish_locked()
        # Abacus residency start (outside the lock: the meter has its
        # own; inert one-comparison no-op unless TPUNN_METER armed)
        meter.on_kv_reserve(seq_id, table)
        return True

    def fork(self, parent_id: str, child_id: str, tokens: int, *,
             shared_tokens: int) -> bool:
        """COW-fork a decode branch off a live parent reservation (the
        Prism n-best choke point — exactly one package call site,
        lint-pinned). The parent's *full* blocks covering
        ``shared_tokens`` prompt rows join the child's table by
        reference (refcounted, exactly like a prefix-cache share: an
        exclusively-owned parent block becomes live-shared, an
        already-shared one gains a sharer); only the child's tail —
        the partial prompt block plus its own generated tokens — comes
        off the free list. n branches therefore hold ONE prompt block
        set + n tails, not n full reservations. False (and no state
        change) when the free list can't cover the tail — the
        scheduler's backpressure signal, same as :meth:`reserve`."""
        with self._lock:
            table = self._tables.get(parent_id)
            if table is None:
                raise KeyError(
                    f"fork parent {parent_id!r} has no reservation")
            shared = list(table[:max(int(shared_tokens), 0)
                                // self.block_size])
        if not self.reserve(child_id, tokens, shared=shared):
            return False
        self._c_branches.inc()
        return True

    def extend(self, seq_id: str, tokens: int) -> None:
        """Advance a sequence's written-token high-water mark. Never
        fails inside the reservation (the no-mid-decode-wall invariant);
        raises if the engine tries to write past what was reserved —
        that is a scheduler bug, not a capacity condition."""
        with self._lock:
            table = self._tables.get(seq_id)
            if table is None:
                raise KeyError(f"sequence {seq_id!r} has no reservation")
            if self.blocks_for(tokens) > len(table):
                raise ValueError(
                    f"sequence {seq_id!r} wrote {tokens} tokens past its "
                    f"{len(table)}-block reservation"
                )
            if tokens > self._used_tokens[seq_id]:
                self._used_tokens[seq_id] = int(tokens)
                self._publish_locked()

    def free(self, seq_id: str,
             retain: frozenset[int] = frozenset()) -> int:
        """Return a finished sequence's blocks to the pool; returns the
        block count that reached the free list. Freeing an unknown id
        is a no-op (retire paths race benignly with cancel paths).

        Blocks still held by another sharer just drop a refcount and
        stay live. Zero-ref blocks named in ``retain`` park in the
        cached-LRU ring (table order, so the prefix chain ages
        coherently) instead of going free — the donation half of the
        prefix cache."""
        with self._lock:
            table = self._tables.pop(seq_id, None)
            self._used_tokens.pop(seq_id, None)
            if not table:
                return 0
            released = []
            parked = []
            for b in table:
                if b in self._ref:
                    self._ref[b] -= 1
                    if self._ref[b] > 0:
                        continue  # another sharer keeps it live
                    del self._ref[b]
                if b in retain:
                    self._cached[b] = None
                    self._cached.move_to_end(b)
                    parked.append(b)
                else:
                    released.append(b)
            self._free.extend(reversed(released))
            self._publish_locked()
        # Abacus residency end: parked (donated) blocks keep billing
        # the donating tenant from the cached ring
        meter.on_kv_free(seq_id, cached=tuple(parked))
        return len(released)

    # -- cached-LRU ring ---------------------------------------------------

    def is_cached(self, block: int) -> bool:
        with self._lock:
            return block in self._cached

    def refcount(self, block: int) -> int:
        """Live sharer count for a shared block (0: cached, free, or
        exclusively owned)."""
        with self._lock:
            return self._ref.get(block, 0)

    def cached_lru(self) -> list[int]:
        """Cached blocks, least-recently-touched first — the prefix
        cache's eviction scan order."""
        with self._lock:
            return list(self._cached)

    def touch_cached(self, block: int) -> None:
        """Refresh a cached block's recency (a peek/partial match that
        did not promote it to live still proves it is useful)."""
        with self._lock:
            if block in self._cached:
                self._cached.move_to_end(block)

    def pin(self, block: int) -> bool:
        """Make a cached block eviction-proof while the engine copies
        its rows (the COW-tail restore window). True if this call
        pinned it, False if it was pinned already (whoever pinned it
        unpins it)."""
        with self._lock:
            fresh = block not in self._pinned
            self._pinned.add(block)
            return fresh

    def unpin(self, block: int) -> None:
        with self._lock:
            self._pinned.discard(block)

    def adopt_cached(self) -> int | None:
        """Pop one free block and park it directly in the cached-LRU
        ring (most-recent end), returning its id — the receiving side
        of KV block streaming (:mod:`serve.disagg`): a peer's prefix
        block lands here already materialized, never owned by a live
        sequence on this replica, and is handed out later exactly like
        a locally-donated block (``reserve(shared=...)``). None — and
        no state change — when the free list is empty: streamed warmth
        must never displace live reservations' headroom."""
        with self._lock:
            if not self._free:
                return None
            b = self._free.pop()
            self._cached[b] = None
            self._cached.move_to_end(b)
            self._publish_locked()
        meter.on_kv_adopt(b)
        return b

    def release_cached(self, block: int) -> bool:
        """Evict one cached block to the free list. False — and no
        state change — when the block is pinned or not cached (already
        evicted, or promoted to live by a sharer in between): the
        prefix cache's eviction scan treats False as "pick another"."""
        with self.releasing_cached() as release:
            return release(block)

    @contextlib.contextmanager
    def releasing_cached(self):
        """One eviction pass: yields ``release(block)``, which is
        :meth:`release_cached` block by block, the same refusals and
        the same free list, while the gauges (a sum over the live
        sequences each time) and the meter are brought up to date
        once, as the pass ends, for all it released."""
        gone: list[int] = []

        def release(block: int) -> bool:
            with self._lock:
                if block in self._pinned or block not in self._cached:
                    return False
                del self._cached[block]
                self._free.append(block)
            gone.append(block)
            return True

        try:
            yield release
        finally:
            if gone:
                with self._lock:
                    self._publish_locked()
                for block in gone:
                    meter.on_kv_evict(block)

    # -- introspection -----------------------------------------------------

    def block_table(self, seq_id: str) -> tuple[int, ...]:
        with self._lock:
            return tuple(self._tables.get(seq_id, ()))

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def cached_blocks(self) -> int:
        with self._lock:
            return len(self._cached)

    @property
    def live_sequences(self) -> int:
        with self._lock:
            return len(self._tables)

    def utilization(self) -> float:
        """Live-reserved fraction of the pool, in [0, 1]. Cached blocks
        are reclaimable, so they count as headroom here even though
        they are off the free list."""
        with self._lock:
            return (self.num_blocks - len(self._free)
                    - len(self._cached)) / self.num_blocks
