"""Content-addressed radix prefix cache over the paged KV pool.

Thousands of requests share system prompts and few-shot prefixes; the
Gemma-on-TPU serving analysis (PAPERS.md) attributes most of the
serving gap to batching policy and KV **residency** — this module is
the residency half. The paged :class:`serve.kv_pool.KVPool` was built
so that sharing a block across sequences is one refcount; this module
decides *which* blocks to share.

Design:

- **content addressing** — a block covering token ids ``t`` whose
  parent block hashed to ``d`` is keyed ``sha1(d + t.tobytes())``. The
  chained digest makes the key a function of the entire prefix, so two
  requests agree on a block id iff they agree on every token up to and
  including it. The index is a radix tree flattened to one dict keyed
  by digest (the chain IS the tree path); explicit parent/children
  links exist only to enforce leaf-only eviction;
- **admission matching** — :meth:`PrefixCache.admit` walks the
  request's full blocks through the index; every resident block is
  shared by reference (refcount++ via ``pool.reserve(shared=)``), so
  the engine restores those rows from the device block store and
  prefills only the suffix. A partial-tail match (the request diverges
  mid-block) is **copy-on-write**: the matched block's content is
  restored but the request's table gets a fresh private block, so the
  donor's block is never written past. At most ``len(prompt) - 1``
  tokens match — at least one token always prefills so the request's
  first-token logits exist;
- **eviction** — a finished sequence donates its full blocks to the
  index (:meth:`release`), which parks refcount-0 blocks in the pool's
  cached LRU ring instead of freeing them. Under allocation pressure,
  admission sheds unpinned LRU **leaf** blocks (children would be
  orphaned by an interior eviction: matching requires a contiguous
  chain from block 0). The COW tail is pinned across the
  match->restore window so a same-round admission cannot evict content
  another admission is about to copy, and the matched chain across the
  admission's own eviction, so making room for the rest of a
  reservation never sheds the blocks it is about to share;
- **accounting** — every index mutation funnels through
  :meth:`PrefixCache._account` (lint-enforced by tests/test_quality
  .py, mirroring the scheduler's ``_transition``): the
  ``serve_kv_prefix_{hits,misses,evictions}_total`` counters, the
  ``serve_kv_prefix_hit_rate`` gauge, the tokens-saved counter, and a
  ``prefix`` flight event can never drift from the index's actual
  shape.

Thread model: the engine thread matches/admits (inside the
scheduler's admission pass) and donates (at retire); client threads
only :meth:`peek` (router affinity), which takes the lock but mutates
nothing.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
from typing import Optional

import numpy as np

from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.obs.span import span
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve.kv_pool import KVPool


def _digest(parent: bytes, tokens: np.ndarray) -> bytes:
    return hashlib.sha1(
        parent + np.asarray(tokens, np.int32).tobytes()).digest()


def _root(adapter: int) -> bytes:
    """Chain seed. The KV content of a block depends on the LoRA
    adapter (its v-projection delta is baked into the cached rows), so
    the content address namespaces the whole chain by adapter id — two
    requests share a block iff they agree on every token AND the
    adapter. Never a valid sha1 digest (wrong length), so roots can't
    collide with interior nodes."""
    return b"a%d|" % int(adapter)


@dataclasses.dataclass
class PrefixMatch:
    """One admission's match: ``blocks`` are shared by reference (they
    are the head of the sequence's block table), ``tail`` is the
    pinned copy-on-write source whose content is restored but whose
    block is NOT in the table, ``tokens`` is the prefill offset m."""

    blocks: tuple[int, ...] = ()
    tail: Optional[int] = None
    tokens: int = 0

    @property
    def restore_blocks(self) -> tuple[int, ...]:
        return self.blocks + ((self.tail,) if self.tail is not None
                              else ())


class _Node:
    __slots__ = ("digest", "parent", "tokens", "phys", "children")

    def __init__(self, digest: bytes, parent: bytes,
                 tokens: np.ndarray, phys: int) -> None:
        self.digest = digest
        self.parent = parent
        self.tokens = np.asarray(tokens, np.int32)
        self.phys = int(phys)
        self.children: set[bytes] = set()


class PrefixCache:
    """Radix index of resident KV blocks, content-addressed."""

    def __init__(self, pool: KVPool, *, max_rows: int = 0,
                 tag: str = "") -> None:
        self.pool = pool
        self.block_size = pool.block_size
        # ceiling on rows the engine's per-row cache can restore into
        # (a COW tail whose block would overflow it is not matched)
        self.max_rows = int(max_rows) or pool.num_blocks * pool.block_size
        self.tag = tag
        self._lock = threading.Lock()
        self._nodes: dict[bytes, _Node] = {}
        self._by_phys: dict[int, bytes] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.scanned = 0
        self.tokens_saved = 0
        reg = get_registry()
        self._c_hits = reg.counter(
            "serve_kv_prefix_hits_total",
            "admissions that matched a resident prefix")
        self._c_misses = reg.counter(
            "serve_kv_prefix_misses_total",
            "admissions with no resident prefix")
        self._c_evictions = reg.counter(
            "serve_kv_prefix_evictions_total",
            "cached prefix blocks evicted under pressure")
        self._c_scanned = reg.counter(
            "serve_kv_prefix_evict_scanned_total",
            "cached-ring entries the eviction walks looked at")
        self._c_saved = reg.counter(
            "serve_kv_prefix_tokens_saved_total",
            "prompt tokens whose prefill was skipped")
        self._g_hit_rate = reg.gauge(
            "serve_kv_prefix_hit_rate",
            "hits / (hits + misses), lifetime")

    # -- the single counted choke point ------------------------------------

    def _account(self, op: str, *, tokens: int = 0,
                 note: str = "") -> None:
        """EVERY prefix-cache state change lands here (lint-enforced):
        the counters, the hit-rate gauge, and the flight ring cannot
        drift from the index's actual mutations."""
        flight.record("prefix", op, note=note or self.tag)
        if op == "hit":
            self.hits += 1
            self.tokens_saved += tokens
            self._c_hits.inc()
            self._c_saved.inc(tokens)
        elif op == "miss":
            self.misses += 1
            self._c_misses.inc()
        elif op == "evict":
            self.evictions += 1
            self._c_evictions.inc()
        total = self.hits + self.misses
        if total:
            self._g_hit_rate.set(self.hits / total)

    # -- matching ----------------------------------------------------------

    def _match_locked(self, prompt: np.ndarray,
                      adapter: int = 0) -> PrefixMatch:
        """Longest resident chain, capped at ``len(prompt) - 1`` tokens
        (>= 1 token must prefill). Read-only."""
        bs = self.block_size
        cap = len(prompt) - 1
        blocks: list[int] = []
        root = _root(adapter)
        parent = root
        j = 0
        while (j + 1) * bs <= cap:
            d = _digest(parent, prompt[j * bs:(j + 1) * bs])
            node = self._nodes.get(d)
            if node is None:
                break
            blocks.append(node.phys)
            parent = d
            j += 1
        # partial tail: the request diverges inside the next block —
        # restore a child block's content copy-on-write when its first
        # t tokens agree (and the extra block still fits the row cache)
        tail, t = None, cap - j * bs
        if 0 < t < bs and (j + 1) * bs <= self.max_rows:
            head = self._nodes.get(parent) if parent != root else None
            kids = (head.children if head is not None
                    else {d for d, n in self._nodes.items()
                          if n.parent == root})
            rest = prompt[j * bs:cap]
            for d in sorted(kids):
                node = self._nodes.get(d)
                if node is not None and np.array_equal(
                        node.tokens[:t], rest):
                    tail = node.phys
                    break
        m = j * bs + (t if tail is not None else 0)
        return PrefixMatch(blocks=tuple(blocks), tail=tail, tokens=m)

    def peek(self, prompt, adapter: int = 0) -> int:
        """Read-only matched-token count (router affinity scoring).
        No counters, no LRU touch, no pins."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 2:
            return 0
        with self._lock:
            return self._match_locked(prompt, adapter).tokens

    def resident_chain(self, prompt, adapter: int = 0) -> PrefixMatch:
        """Read-only full-block resident chain for ``prompt`` — the
        streamable prefix for peer warm-up (:mod:`serve.disagg`).
        Unlike :meth:`admit` there is no COW tail (only whole blocks
        ship between replicas) and nothing is counted or touched; the
        caller pins the returned blocks in the pool across the export
        window so eviction cannot recycle them mid-stream."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 2:
            return PrefixMatch()
        with self._lock:
            m = self._match_locked(prompt, adapter)
        return PrefixMatch(blocks=m.blocks,
                           tokens=len(m.blocks) * self.block_size)

    # -- admission ---------------------------------------------------------

    def admit(self, seq_id: str, prompt, total_tokens: int,
              adapter: int = 0) -> Optional[PrefixMatch]:
        """Match + reserve for one admission. Returns the match (tokens
        may be 0) when the reservation landed, None on backpressure —
        the scheduler treats None exactly like ``pool.reserve`` False.

        The COW tail is pinned here and stays pinned until the engine
        finishes restoring (:meth:`finish_restore`)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._lock:
            if chaos.on_prefix_evict():
                self._evict_locked(1)
            with span("serve/prefix_match") as sp:
                match = self._match_locked(prompt, adapter)
                sp.set(blocks=len(match.blocks))
            if match.tail is not None:
                self.pool.pin(match.tail)
            need = (self.pool.blocks_for(total_tokens)
                    - len(match.blocks))
            short = need - self.pool.free_blocks
            if short > 0:
                # the matched chain sits in the cached ring until the
                # reserve below makes it live, and its last block is an
                # LRU leaf like any other: shed to make room for the
                # rest of this very reservation, it left ``reserve`` a
                # block the index no longer knew ("the prefix index is
                # stale", which killed the serve loop)
                with span("serve/evict") as sp:
                    mine = [b for b in match.blocks if self.pool.pin(b)]
                    before = self.scanned
                    sp.set(blocks=self._evict_locked(short),
                           scanned=self.scanned - before)
                    for b in mine:
                        self.pool.unpin(b)
            if not self.pool.reserve(seq_id, total_tokens,
                                     shared=match.blocks):
                if match.tail is not None:
                    self.pool.unpin(match.tail)
                self._account("defer", note=seq_id)
                return None
            if match.tokens > 0:
                self._account("hit", tokens=match.tokens,
                              note=f"{seq_id} m={match.tokens}")
            else:
                self._account("miss", note=seq_id)
            return match

    def make_room(self, blocks: int) -> int:
        """Shed up to ``blocks`` unpinned LRU cached blocks to the free
        list, returning the count actually shed. Branch tails
        (:meth:`KVPool.fork`) allocate straight off the free list,
        bypassing :meth:`admit`'s reclaim — the scheduler calls this
        before retrying a fork that found the free list parked in the
        cached ring."""
        with self._lock:
            return self._evict_locked(int(blocks))

    def finish_restore(self, match: PrefixMatch) -> None:
        """Unpin the COW tail once its content has been copied into the
        admitting sequence's rows."""
        if match.tail is None:
            return
        with self._lock:
            self.pool.unpin(match.tail)
            self._account("unpin", note=f"b{match.tail}")

    # -- donation + eviction -----------------------------------------------

    def release(self, seq_id: str, tokens, adapter: int = 0) -> int:
        """Retire-side: index the finished sequence's full blocks
        (dedup by digest — a block whose chain is already resident is
        not re-indexed) and free its table, retaining exactly the
        indexed blocks in the pool's cached ring. Returns the count of
        blocks that actually hit the free list."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        bs = self.block_size
        root = _root(adapter)
        with self._lock:
            table = self.pool.block_table(seq_id)
            retain: set[int] = set()
            parent = root
            for j in range(min(len(tokens) // bs, len(table))):
                d = _digest(parent, tokens[j * bs:(j + 1) * bs])
                node = self._nodes.get(d)
                if node is None:
                    node = _Node(d, parent, tokens[j * bs:(j + 1) * bs],
                                 table[j])
                    self._nodes[d] = node
                    self._by_phys[node.phys] = d
                    head = (self._nodes.get(parent)
                            if parent != root else None)
                    if head is not None:
                        head.children.add(d)
                    self._account("donate",
                                  note=f"{seq_id} b{node.phys}")
                if node.phys == table[j]:
                    retain.add(table[j])
                parent = d
            return self.pool.free(seq_id, retain=frozenset(retain))

    def ingest(self, tokens, adapter: int = 0) -> list[tuple[int, int]]:
        """Receive side of KV block streaming (:mod:`serve.disagg`):
        index ``tokens``'s full blocks as resident, adopting a
        cached-ring block (:meth:`KVPool.adopt_cached`) for each one
        the radix does not already hold. Returns ``[(chain_pos, phys)]``
        for the newly-indexed blocks — the ones whose streamed bytes
        still need writing into the device block store
        (already-resident blocks dedup by digest, exactly like
        :meth:`release`). Stops early, indexing a shorter chain, when
        the pool has no free block to adopt and nothing unpinned to
        shed — streamed warmth never displaces live reservations."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        bs = self.block_size
        root = _root(adapter)
        plan: list[tuple[int, int]] = []
        with self._lock:
            parent = root
            for j in range(len(tokens) // bs):
                blk = tokens[j * bs:(j + 1) * bs]
                d = _digest(parent, blk)
                node = self._nodes.get(d)
                if node is None:
                    phys = self.pool.adopt_cached()
                    if phys is None:
                        if not self._evict_locked(1):
                            break
                        phys = self.pool.adopt_cached()
                        if phys is None:
                            break
                    node = _Node(d, parent, blk, phys)
                    self._nodes[d] = node
                    self._by_phys[phys] = d
                    head = (self._nodes.get(parent)
                            if parent != root else None)
                    if head is not None:
                        head.children.add(d)
                    self._account("ingest", note=f"b{phys}")
                    plan.append((j, phys))
                parent = d
        return plan

    def abandon(self, seq_id: str) -> int:
        """Failure-path release: free the sequence's table without
        indexing anything new, but retain blocks the index already
        maps (shared prefix blocks owned by a resident chain) so a
        failed sequence can't yank content out from under the radix."""
        with self._lock:
            table = self.pool.block_table(seq_id)
            retain = frozenset(b for b in table if b in self._by_phys)
            self._account("abandon", note=seq_id)
            return self.pool.free(seq_id, retain=retain)

    def _evict_locked(self, need: int) -> int:
        """Shed up to ``need`` blocks, each time the least-recently-
        parked one that is cached, unpinned and has no indexed child,
        in one walk of the ring. A chain parks root first, so the walk
        steps over its interior to reach the leaf; once that is shed
        its parent is the oldest leaf if the walk has stepped over it
        (nothing else behind the cursor changed), and the chain is
        climbed from there. A parent still ahead is met in its turn.
        Counted per block through :meth:`_account`."""
        if need <= 0:
            return 0
        shed = scanned = 0
        behind: set[int] = set()  # stepped over: parked, before the cursor
        with self.pool.releasing_cached() as release:

            def sheds(node: _Node) -> bool:
                if node.children & self._nodes.keys():
                    return False  # interior: evicting orphans descendants
                if not release(node.phys):
                    return False  # pinned (a COW restore in flight)
                self._drop_locked(node)
                self._account("evict", note=f"b{node.phys}")
                return True

            for phys in self.pool.cached_lru():
                scanned += 1
                d = self._by_phys.get(phys)
                if d is None:
                    # cached but never indexed (shouldn't happen):
                    # reclaim it anyway
                    shed += release(phys)
                    continue
                node = self._nodes[d]
                if not sheds(node):
                    behind.add(phys)
                    continue
                shed += 1
                while shed < need:
                    node = self._nodes.get(node.parent)
                    if node is None or node.phys not in behind:
                        break  # a root; a parent live, or still ahead
                    scanned += 1
                    if not sheds(node):
                        break
                    shed += 1
                if shed >= need:
                    break
        self.scanned += scanned
        self._c_scanned.inc(scanned)
        return shed

    def _drop_locked(self, node: _Node) -> None:
        del self._nodes[node.digest]
        self._by_phys.pop(node.phys, None)
        head = self._nodes.get(node.parent) if node.parent else None
        if head is not None:
            head.children.discard(node.digest)

    # -- introspection -----------------------------------------------------

    @property
    def nodes(self) -> int:
        with self._lock:
            return len(self._nodes)

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return dict(
                prefix_hits=self.hits, prefix_misses=self.misses,
                prefix_evictions=self.evictions,
                prefix_tokens_saved=self.tokens_saved,
                prefix_hit_rate=(self.hits / total if total else 0.0),
                prefix_nodes=len(self._nodes),
            )
