"""Prefix cache + multi-tenant serving (ISSUE 9 tentpole).

Bottom-up over the new surface: KVPool block sharing (refcounts,
cached-LRU parking, pins), the content-addressed PrefixCache (radix
matching through chained digests, copy-on-write tails, LRU eviction,
per-adapter namespaces), the engine goldens (prefix cache ON must be
bit-identical to OFF and to sequential ``generate`` — including COW
divergence mid-block and re-prefill after eviction), per-request LoRA
adapters against the merged-weights oracle, DRR tenant fairness + the
quota starvation regression, router prefix affinity, chaos drills
(``evict_prefix`` / ``tenant_flood``), and the per-tenant watchtower
burn page naming the burning tenant.
"""

import time

import numpy as np
import pytest

import jax

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.inference.generate import generate
from pytorch_distributed_nn_tpu.nn.lora import init_lora_bank, merge_lora
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.obs.watchtower import (
    PAGE,
    WatchConfig,
    Watchtower,
)
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import (
    KVPool,
    PrefixCache,
    Router,
    Scheduler,
    ServingEngine,
)
from pytorch_distributed_nn_tpu.serve.router import READY

VOCAB = 97


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Disarmed chaos, fresh flight ring + metric registry per test."""
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    monkeypatch.delenv(chaos.ENV_CHAOS_SEED, raising=False)
    chaos.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    yield
    chaos.reset()


# tiny_llama comes from conftest.py (session-scoped): one model shared
# across the serving test files so the serve jits compile once.


def _ref(model, params, prompt, n_new):
    out = np.asarray(generate(model, params,
                              np.asarray(prompt, np.int32)[None], n_new))
    return out[0, len(prompt):]


def _prefix_ring_ops():
    return [e["op"] for e in flight.get_recorder().snapshot()
            if e["kind"] == "prefix"]


# ---------------------------------------------------------------------------
# KVPool: shared blocks, cached-LRU parking, pins
# ---------------------------------------------------------------------------

def test_pool_shared_blocks_refcount_and_cached_parking():
    pool = KVPool(num_blocks=8, block_size=4)
    assert pool.reserve("a", 12)  # 3 blocks
    table = pool.block_table("a")
    # free with retain: zero-ref blocks park cached, the rest go free
    pool.free("a", retain=frozenset(table[:2]))
    assert pool.cached_blocks == 2 and pool.free_blocks == 6
    assert pool.is_cached(table[0]) and not pool.is_cached(table[2])

    # reserve sharing the cached prefix: cached -> live, refcount 1
    assert pool.reserve("b", 12, shared=table[:2])
    assert pool.cached_blocks == 0
    assert pool.refcount(table[0]) == 1
    assert pool.block_table("b")[:2] == table[:2]
    # a second sharer bumps the refcount without allocating
    assert pool.reserve("c", 12, shared=table[:2])
    assert pool.refcount(table[0]) == 2
    # first free decrements; blocks stay live for the survivor
    pool.free("b")
    assert pool.refcount(table[0]) == 1
    assert not pool.is_cached(table[0])
    # last free with retain parks them cached again
    pool.free("c", retain=frozenset(table[:2]))
    assert pool.cached_blocks == 2
    assert pool.live_sequences == 0


def test_pool_pin_blocks_eviction_and_lru_order():
    pool = KVPool(num_blocks=4, block_size=4)
    assert pool.reserve("a", 16)
    t = pool.block_table("a")
    pool.free("a", retain=frozenset(t))
    assert pool.cached_lru() == list(t)  # oldest first
    pool.touch_cached(t[0])              # refresh recency
    assert pool.cached_lru() == list(t[1:]) + [t[0]]
    pool.pin(t[1])
    assert not pool.release_cached(t[1])  # pinned: refused
    pool.unpin(t[1])
    assert pool.release_cached(t[1])
    assert not pool.release_cached(t[1])  # already free: refused
    assert pool.free_blocks == 1


# ---------------------------------------------------------------------------
# PrefixCache: radix matching, COW tails, eviction, adapter namespaces
# ---------------------------------------------------------------------------

def test_prefix_match_donate_hit_and_last_token_cap():
    pool = KVPool(num_blocks=16, block_size=4)
    pc = PrefixCache(pool, max_rows=64)
    prompt = np.arange(1, 13, dtype=np.int32)  # 12 tokens, 3 blocks

    m = pc.admit("a", prompt, 16)
    assert m is not None and m.tokens == 0   # cold: full prefill
    pc.release("a", prompt)                  # donate covered blocks

    # the same prompt re-matches at most L-1 tokens (the engine must
    # run at least one real forward step to emit the first token)
    m2 = pc.admit("b", prompt, 16)
    assert m2 is not None and m2.tokens == 11
    assert len(m2.blocks) == 2 and m2.tail is not None
    pc.finish_restore(m2)
    st = pc.stats()
    assert st["prefix_hits"] == 1 and st["prefix_misses"] == 1
    assert st["prefix_tokens_saved"] == 11
    ops = _prefix_ring_ops()
    assert "miss" in ops and "hit" in ops and "donate" in ops


def test_prefix_cow_divergence_mid_block():
    pool = KVPool(num_blocks=16, block_size=4)
    pc = PrefixCache(pool, max_rows=64)
    p1 = np.arange(1, 13, dtype=np.int32)
    pc.admit("a", p1, 16)
    pc.release("a", p1)

    # ends inside the donor's third block: 2 full blocks match whole,
    # the third contributes a 2-row copy-on-write tail (rows 8..9)
    p2 = np.concatenate([p1[:10], np.asarray([99], np.int32)])
    m = pc.admit("b", p2, 16)
    assert m is not None and m.tokens == 10
    assert len(m.blocks) == 2 and m.tail is not None
    # the COW tail stays pinned until the engine finished copying it
    assert not pool.release_cached(m.tail)
    pc.finish_restore(m)
    # ...and b's own table does NOT alias the donor's tail block: its
    # third block is a fresh allocation (divergent rows never share)
    assert pool.block_table("b")[2] != m.tail

    # divergence BELOW the cap inside a block degrades to whole-block
    # matching — never a wrong-content tail
    p3 = np.concatenate([p1[:10], np.asarray([90, 91], np.int32)])
    m3 = pc.admit("c", p3, 16)
    assert m3 is not None and m3.tokens == 8 and m3.tail is None


def test_prefix_eviction_under_pressure_then_re_prefill():
    pool = KVPool(num_blocks=4, block_size=4)
    pc = PrefixCache(pool, max_rows=16)
    p1 = np.arange(1, 9, dtype=np.int32)   # 2 blocks
    pc.admit("a", p1, 8)
    pc.release("a", p1)
    assert pool.cached_blocks == 2

    # a cold sequence needing the whole pool: the cached blocks are
    # evicted (counted) to cover the reservation
    p2 = np.asarray([50, 51, 52, 53, 54, 55, 56, 57], np.int32)
    m = pc.admit("b", p2, 16)              # 4 blocks: needs both back
    assert m is not None and m.tokens == 0
    assert pc.stats()["prefix_evictions"] == 2
    assert "evict" in _prefix_ring_ops()
    pc.release("b", p2)

    # hit-after-eviction is a MISS again: the index dropped the nodes
    # with the blocks, so the old prompt re-prefills from scratch
    m3 = pc.admit("c", p1, 8)
    assert m3 is not None and m3.tokens == 0
    assert pc.stats()["prefix_misses"] == 3


def test_admission_never_evicts_the_chain_it_just_matched():
    """The stale-index failure (PERF.md sec. 7, met by a closed loop at
    a small pool; ISSUE 35): a request matches a chain that sits whole
    in the cached ring and needs more fresh blocks than are free, so
    ``admit`` sheds LRU leaves to make room; the matched chain's own
    last block is such a leaf. Shed, its index entry went and
    ``KVPool.reserve`` raised "the prefix index is stale", which killed
    the serve loop. The matched blocks are now pinned across the
    eviction: with nothing else to shed the admission is deferred
    (backpressure), and lands as a hit once a live sequence has left."""
    pool = KVPool(num_blocks=6, block_size=4)
    pc = PrefixCache(pool)
    doc = np.arange(100, 112, dtype=np.int32)         # three full blocks
    assert pc.admit("a", doc, 12) is not None
    pc.release("a", doc)                              # 3 cached, 3 free
    assert pool.cached_blocks == 3 and pool.free_blocks == 3
    assert pool.reserve("live", 8)                    # 1 free
    asked = np.concatenate([doc, np.arange(5, dtype=np.int32)])
    # 5 blocks: 3 matched + 2 fresh, 1 free: one short
    assert pc.admit("b", asked, 20) is None           # deferred, not raised
    assert pc.peek(asked) == 12                       # the chain is whole
    assert pool.cached_blocks == 3 and pool.free_blocks == 1
    pool.free("live")
    match = pc.admit("b", asked, 20)
    assert match is not None and match.tokens == 12
    assert pool.block_table("b")[:3] == match.blocks
    # a colder chain beside it is what gets shed instead
    pc.release("b", asked)
    other = np.arange(200, 208, dtype=np.int32)
    assert pc.admit("c", other, 8) is not None
    pc.release("c", other)
    pc.peek(other)
    assert pool.reserve("live", 4 * pool.free_blocks)  # no block free
    assert pool.free_blocks == 0
    again = np.concatenate([other, np.arange(9, dtype=np.int32)])
    match = pc.admit("d", again, 20)                   # 2 matched + 3 fresh
    assert match is not None and match.tokens == 8
    assert pc.peek(other) == 7 and pc.peek(asked) < 16  # b's tail went


def test_admission_leaves_another_admissions_pin_in_place():
    """``admit`` pins the chain it matched only across its own eviction
    and unpins only what it pinned itself (``KVPool.pin`` says which):
    a block another admission holds pinned for its copy-on-write restore
    is still pinned when this admission has shed what it needed."""
    pool = KVPool(num_blocks=8, block_size=4)
    pc = PrefixCache(pool)
    doc = np.arange(100, 112, dtype=np.int32)         # three full blocks
    other = np.arange(200, 208, dtype=np.int32)       # two
    assert pc.admit("a", doc, 12) is not None
    chain = list(pool.block_table("a"))
    pc.release("a", doc)
    assert pc.admit("c", other, 8) is not None
    pc.release("c", other)                            # 5 cached, 3 free
    assert pool.pin(chain[2]) is True                 # somebody's restore
    assert pool.pin(chain[2]) is False                # pinned already
    assert pool.reserve("live", 12) and pool.free_blocks == 0
    asked = np.concatenate([doc, np.arange(5, dtype=np.int32)])
    match = pc.admit("b", asked, 20)                  # 3 matched + 2 fresh
    assert match is not None and list(match.blocks) == chain
    assert pc.stats()["prefix_evictions"] == 2 and pc.peek(other) == 0
    pc.finish_restore(match)
    pc.release("b", asked)                            # parked again
    assert pool.pin(chain[0]) is True                 # its own pins went
    pool.unpin(chain[0])
    assert pool.is_cached(chain[2]) \
        and not pool.release_cached(chain[2])         # the other's stayed
    pool.unpin(chain[2])


def test_prefix_adapter_namespaces_do_not_cross_match():
    """A prefix cached under one LoRA adapter must never satisfy a
    request for another: cached V rows embed the adapter's v-delta, so
    a cross-adapter hit would replay the wrong weights (the bug the
    digest-chain root namespace exists to prevent)."""
    pool = KVPool(num_blocks=16, block_size=4)
    pc = PrefixCache(pool, max_rows=64)
    prompt = np.arange(1, 13, dtype=np.int32)
    pc.admit("a", prompt, 16, adapter=0)
    pc.release("a", prompt, adapter=0)
    assert pc.peek(prompt, adapter=0) == 11
    assert pc.peek(prompt, adapter=1) == 0   # other adapter: cold
    m = pc.admit("b", prompt, 16, adapter=1)
    assert m is not None and m.tokens == 0


def test_prefix_abandon_keeps_pool_consistent():
    pool = KVPool(num_blocks=8, block_size=4)
    pc = PrefixCache(pool, max_rows=32)
    prompt = np.arange(1, 9, dtype=np.int32)
    pc.admit("a", prompt, 8)
    pc.abandon("a")  # failure path: no index entries for dead rows
    assert pool.live_sequences == 0
    assert pc.peek(prompt) == 0
    assert pool.free_blocks == 8


# ---------------------------------------------------------------------------
# Parity fixture: scripted workloads vs a reference model (the
# test_store_parity pattern — the real radix/pool can never drift from
# the simple model of what matching and block accounting MUST do)
# ---------------------------------------------------------------------------

def _common_len(a, b) -> int:
    n = min(len(a), len(b))
    for i in range(n):
        if int(a[i]) != int(b[i]):
            return i
    return n


def _ref_match(chains, prompt, bs: int) -> int:
    """Reference prediction of ``PrefixMatch.tokens``: ``chains`` is
    every indexed covered-token sequence (block-quantized, as release
    indexes them). The radix walk is greedy full blocks then one COW
    tail, and every radix root-path is a prefix of some released
    chain, so the expected match is a pure function of the chains."""
    cap = len(prompt) - 1
    full = 0
    for c in chains:
        common = _common_len(c, prompt)
        full = max(full, min(common // bs, cap // bs) * bs)
    t = cap - full
    if 0 < t < bs and any(_common_len(c, prompt) >= cap
                          for c in chains):
        return full + t
    return full


def test_parity_scripted_workload_matches_reference_model():
    bs = 4
    pool = KVPool(num_blocks=128, block_size=bs)
    pc = PrefixCache(pool, max_rows=64)
    rng = np.random.default_rng(11)
    bases = [rng.integers(1, VOCAB, size=12).astype(np.int32)
             for _ in range(3)]
    chains: dict = {0: [], 1: []}  # adapter -> indexed token tuples
    hits = misses = saved = 0
    for i in range(24):
        adapter = int(rng.integers(0, 2))
        head = bases[int(rng.integers(0, 3))][:int(rng.integers(4, 13))]
        suffix = rng.integers(1, VOCAB,
                              size=int(rng.integers(1, 9)))
        prompt = np.concatenate([head, suffix]).astype(np.int32)
        exp = _ref_match(chains[adapter], prompt, bs)
        m = pc.admit(f"s{i}", prompt, len(prompt) + 2, adapter=adapter)
        assert m is not None  # 128 blocks: never deferred
        assert m.tokens == exp, (i, list(prompt), exp, m.tokens)
        hits += 1 if exp > 0 else 0
        misses += 0 if exp > 0 else 1
        saved += exp
        pc.finish_restore(m)
        if rng.random() < 0.8:
            covered = np.concatenate(
                [prompt, rng.integers(1, VOCAB, size=1)]
            ).astype(np.int32)
            pc.release(f"s{i}", covered, adapter=adapter)
            chains[adapter].append(tuple(
                int(x) for x in covered[:len(covered) // bs * bs]))
        else:
            pc.abandon(f"s{i}")
        # block conservation after every op: nothing live between
        # ops, so free + cached must cover the whole pool
        assert pool.live_sequences == 0
        assert pool.free_blocks + pool.cached_blocks == pool.num_blocks
    s = pc.stats()
    assert s["prefix_evictions"] == 0  # the reference assumes no evicts
    assert s["prefix_hits"] == hits and s["prefix_misses"] == misses
    assert s["prefix_tokens_saved"] == saved
    assert hits >= 5 and misses >= 5  # the script exercises both paths


def test_parity_accounting_invariant_under_eviction_pressure():
    """Same conservation law when the pool is small enough that admits
    pre-evict cached chains: defer is allowed (None), but blocks can
    never leak — free + cached always re-covers the pool once nothing
    is live."""
    pool = KVPool(num_blocks=8, block_size=4)
    pc = PrefixCache(pool, max_rows=32)
    rng = np.random.default_rng(7)
    admitted = 0
    for i in range(16):
        prompt = rng.integers(
            1, VOCAB, size=int(rng.integers(6, 14))).astype(np.int32)
        m = pc.admit(f"e{i}", prompt, len(prompt) + 1)
        if m is not None:
            admitted += 1
            pc.finish_restore(m)
            pc.release(f"e{i}", prompt)
        assert pool.live_sequences == 0
        assert pool.free_blocks + pool.cached_blocks == pool.num_blocks
    assert admitted >= 8
    assert pc.stats()["prefix_evictions"] > 0


# ---------------------------------------------------------------------------
# Eviction: one walk of the ring against the rule it computes (ISSUE 48)
# ---------------------------------------------------------------------------

def _evict_restart_every_block(self, need: int) -> int:
    """The rule, plainly (the routine as it stood before ISSUE 48):
    for every block, a fresh copy of the ring walked from its oldest
    end to the first cached, unpinned block with no indexed child;
    shed that one, start over."""
    shed = 0
    progress = True
    while shed < need and progress:
        progress = False
        for phys in self.pool.cached_lru():
            self.scanned += 1
            d = self._by_phys.get(phys)
            if d is None:
                if self.pool.release_cached(phys):
                    shed += 1
                    progress = True
                continue
            node = self._nodes[d]
            if node.children & self._nodes.keys():
                continue
            if not self.pool.release_cached(phys):
                continue
            self._drop_locked(node)
            self._account("evict", note=f"b{phys}")
            shed += 1
            progress = True
            break
    return shed


# what a script draws from, and how often it does each thing beside
# admitting and releasing
_EVICT_SCRIPTS = {
    "disjoint_chains": dict(trunk=0),
    "shared_trunk_and_forks": dict(trunk=5),
    "pinned_cow_tail": dict(trunk=3, mid_block=0.9, hold_restore=0.8),
    "pinned_matched_chain": dict(trunk=4, export_pin=0.15),
    "live_parent_cached_child": dict(trunk=6, cut_short=0.6, linger=True),
    "never_indexed_block": dict(trunk=2, stray=0.15),
    "need_beyond_what_can_be_shed": dict(trunk=3, linger=True, flood=0.6),
}


_OPS = ("leave", "make_room", "ingest", "stray", "export_pin", "admit")


def _run_evict_script(kind: str, seed: int, reference: bool):
    """One seeded script of admit / release / abandon / make_room /
    ingest on a small pool. Returns what an observer can tell, op by
    op (the op's result, its victims in order from the flight ring,
    ``stats()``, the free list and the ring), and how many eviction
    passes met the thing ``kind`` is named for."""
    cfg = _EVICT_SCRIPTS[kind]
    bs = 4
    rng = np.random.default_rng(seed)
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    pool = KVPool(num_blocks=40, block_size=bs)
    pc = PrefixCache(pool)
    if reference:
        pc._evict_locked = _evict_restart_every_block.__get__(pc)
    trunks = [rng.integers(1, VOCAB, size=cfg["trunk"] * bs)
              for _ in range(2)]

    def draw_doc(i):  # some whole blocks of a trunk, then its own
        head = trunks[i % 2][:int(rng.integers(cfg["trunk"] + 1)) * bs]
        own = rng.integers(1, VOCAB, size=int(rng.integers(2, 9)) * bs)
        return np.concatenate([head, own]).astype(np.int32)

    docs = [draw_doc(i) for i in range(8)]
    odds = [0.22 if cfg.get("linger") else 0.40, 0.08, 0.06,
            cfg.get("stray", 0.0), cfg.get("export_pin", 0.0)]
    odds.append(1.0 - sum(odds))
    live: dict = {}     # seq -> (prompt, match or None once restored)
    exported: list = []  # blocks this script pinned, as an export does
    log, met = [], 0

    def witness() -> bool:
        ring = set(pool.cached_lru())
        if kind == "never_indexed_block":
            return bool(ring - pc._by_phys.keys())
        if kind in ("pinned_cow_tail", "pinned_matched_chain"):
            return bool(ring & pool._pinned)
        if kind == "live_parent_cached_child":
            return any(n.phys in ring and n.parent in pc._nodes
                       and pc._nodes[n.parent].phys not in ring
                       for n in pc._nodes.values())
        return True

    def settle(seq):
        prompt, match = live.pop(seq)
        if match is not None:
            pc.finish_restore(match)
        return prompt

    for i in range(140):
        flight.reset_recorder(enabled=True)
        had, before = witness(), pc.evictions + pool.free_blocks
        what = str(rng.choice(_OPS, p=odds))
        if what == "leave" and live:
            seq = sorted(live)[int(rng.integers(len(live)))]
            prompt = settle(seq)
            if rng.random() < 0.85:
                op = ("release", pc.release(seq, np.concatenate(
                    [prompt, rng.integers(1, VOCAB, size=3)])))
            else:
                op = ("abandon", pc.abandon(seq))
        elif what == "stray" and pool.free_blocks >= 2:
            # parked by the pool alone: cached, never indexed
            assert pool.reserve(f"u{i}", 2 * bs)
            pool.free(f"u{i}", retain=frozenset(pool.block_table(f"u{i}")))
            op = ("stray", 2)
        elif what == "export_pin":
            # as serve.disagg pins a chain across its export window
            for b in exported:
                pool.unpin(b)
            chain = pc.resident_chain(docs[int(rng.integers(8))]).blocks
            exported[:] = [b for b in chain if pool.pin(b)]
            op = ("export_pin", len(exported))
        elif what == "make_room":
            n = 10 ** 6 if rng.random() < cfg.get("flood", 0.1) \
                else int(rng.integers(1, 7))
            op = ("make_room", pc.make_room(n))
        elif what == "ingest":
            doc = docs[int(rng.integers(8))]
            op = ("ingest", pc.ingest(
                doc[:int(rng.integers(1, len(doc) // bs + 1)) * bs]))
        else:
            doc = docs[int(rng.integers(8))]
            if rng.random() < cfg.get("cut_short", 0.2):
                doc = doc[:int(rng.integers(1, len(doc) // bs + 1)) * bs + 1]
            if rng.random() < cfg.get("mid_block", 0.3):
                doc = doc[:max(2, len(doc) - int(rng.integers(1, bs)))]
            match = pc.admit(f"s{i}", doc, len(doc) + int(
                rng.integers(1, 3 * bs)))
            if match is not None:
                hold = rng.random() < cfg.get("hold_restore", 0.2)
                if not hold:
                    pc.finish_restore(match)
                live[f"s{i}"] = (doc, match if hold else None)
            op = ("admit", match)
        victims = [e["note"] for e in flight.get_recorder().snapshot()
                   if e["kind"] == "prefix" and e["op"] == "evict"]
        if kind == "need_beyond_what_can_be_shed":
            had = op == ("admit", None) or (
                what == "make_room" and op[1] < n)
        if had and pc.evictions + pool.free_blocks > before:
            met += 1
        log.append((op, victims, pc.stats(), list(pool._free),
                    pool.cached_lru()))
    for b in exported:
        pool.unpin(b)
    for seq in sorted(live):
        settle(seq)
        pc.abandon(seq)
    assert pool.live_sequences == 0 and not pool._pinned
    assert pool.free_blocks + pool.cached_blocks == pool.num_blocks
    return log, met, pc.scanned


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("kind", sorted(_EVICT_SCRIPTS))
def test_one_walk_sheds_what_restarting_every_block_sheds(kind, seed):
    want, met, restarts = _run_evict_script(kind, seed, reference=True)
    got, _, walk = _run_evict_script(kind, seed, reference=False)
    assert len(want) == len(got)
    for i, (a, b) in enumerate(zip(want, got)):
        assert a == b, (i, a[0], a[1], b[0], b[1])
    shed = want[-1][2]["prefix_evictions"]
    assert shed >= 40 and met >= 3, (shed, met)
    assert walk <= restarts


def test_an_admissions_eviction_looks_at_the_ring_once():
    """The cost by count, as A.X-K1's documents park: a pool of 8,192
    blocks full of 417-block chains, root first. One admission that
    sheds ~400 blocks looks at each ring entry at most once and at
    each block it sheds once more; restarting at the oldest end for
    every block, the same admission looks 82,158 times."""
    bs, chain = 16, 417
    pool = KVPool(num_blocks=8192, block_size=bs)
    pc = PrefixCache(pool)
    rng = np.random.default_rng(48)
    for i in range(8192 // chain + 1):
        doc = rng.integers(1, 30000, size=chain * bs + 5).astype(np.int32)
        assert pc.admit(f"d{i}", doc, len(doc)) is not None
        pc.release(f"d{i}", doc)
    ring = pool.cached_blocks
    assert pool.free_blocks < chain and ring > 7500
    rec = obs.enable_tracing()
    try:
        doc = rng.integers(1, 30000, size=400 * bs).astype(np.int32)
        shed0, scanned0 = pc.evictions, pc.scanned
        assert pc.admit("cold", doc, len(doc)) is not None
    finally:
        obs.disable_tracing()
    (ev,) = [e for e in rec.events() if e["name"] == "serve/evict"]
    shed = pc.evictions - shed0
    assert 300 <= shed <= 400 and ev["args"]["blocks"] == shed
    assert ev["args"]["scanned"] == pc.scanned - scanned0
    assert shed <= ev["args"]["scanned"] <= ring + shed
    assert ev["args"]["scanned"] <= 3 * shed  # 82,158 before
    total = obs.get_registry().snapshot()
    assert total["serve_kv_prefix_evict_scanned_total"] == pc.scanned


# ---------------------------------------------------------------------------
# Engine goldens: cache ON == cache OFF == sequential generate
# ---------------------------------------------------------------------------

def _shared_prefix_prompts():
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, VOCAB, size=(24,)).astype(np.int32)
    suffixes = [rng.integers(1, VOCAB, size=(n,)).astype(np.int32)
                for n in (5, 3, 7, 4)]
    wave1 = [np.concatenate([prefix, suffixes[0]])]
    wave2 = [np.concatenate([prefix, s]) for s in suffixes[1:]]
    # COW mid-block: shares 26 tokens (3 full 8-blocks + 2 rows into
    # the fourth), then diverges inside that block
    cow = np.concatenate([wave1[0][:26],
                          np.asarray([7, 9, 11], np.int32)])
    wave2.append(cow)
    return wave1, wave2


def _run_engine(model, params, prompts_by_wave, n_new, **kw):
    eng = ServingEngine(model, params, max_slots=3, max_seq_len=64,
                        block_size=8, max_queue=16, **kw)
    outs = []
    for wave in prompts_by_wave:
        reqs = [eng.submit(p, n_new) for p in wave]
        eng.run_until_idle()
        for r in reqs:
            assert r.state == "done", (r.state, r.reject_reason)
            outs.append(np.asarray(r.tokens))
    return eng, outs


def test_engine_golden_prefix_on_equals_off_equals_generate(tiny_llama):
    """The acceptance criterion: a prefix-cache hit restores bit-copied
    KV rows, so greedy outputs with the cache ON are identical to OFF
    and to a solo sequential generate — including the COW-tail request
    that diverges mid-block."""
    model, params = tiny_llama
    wave1, wave2 = _shared_prefix_prompts()
    n_new = 6

    eng_on, outs_on = _run_engine(model, params, (wave1, wave2), n_new,
                                  prefix_cache=True)
    eng_off, outs_off = _run_engine(model, params, (wave1, wave2), n_new,
                                    prefix_cache=False)
    for p, a, b in zip(wave1 + wave2, outs_on, outs_off):
        ref = _ref(model, params, p, n_new)
        np.testing.assert_array_equal(a, ref)
        np.testing.assert_array_equal(b, ref)

    st = eng_on.prefix_cache.stats()
    assert st["prefix_hits"] >= len(wave2)
    assert st["prefix_tokens_saved"] >= 24 * len(wave2)
    assert eng_on.summary()["prefix_hit_rate"] > 0, eng_on.summary()
    assert eng_off.prefix_cache is None
    # every completed request reports what it skipped
    cached = [c.get("cached_tokens", 0) for c in eng_on.completed]
    assert sum(1 for c in cached if c > 0) >= len(wave2)
    assert "hit" in _prefix_ring_ops()
    # nothing leaks: retired blocks are cached or free, never live
    assert eng_on.scheduler.pool.live_sequences == 0


@pytest.mark.slow  # ~7s: two full waves re-prefilled under p=1 shedding
def test_engine_chaos_evict_prefix_is_correctness_neutral(tiny_llama):
    """The residency drill sheds cached blocks at every admission; hits
    degrade to misses but outputs must stay golden (eviction can cost
    prefill, never correctness)."""
    model, params = tiny_llama
    chaos.maybe_init("evict_prefix@p=1", rank=0, seed=0)
    wave1, wave2 = _shared_prefix_prompts()
    n_new = 4
    eng, outs = _run_engine(model, params, (wave1, wave2), n_new,
                            prefix_cache=True)
    for p, a in zip(wave1 + wave2, outs):
        np.testing.assert_array_equal(a, _ref(model, params, p, n_new))
    assert eng.prefix_cache.stats()["prefix_evictions"] >= 1


def test_engine_tenant_flood_injects_synthetic_requests(tiny_llama):
    model, params = tiny_llama
    chaos.maybe_init("tenant_flood@tenant=burst:rps=50", rank=0, seed=0)
    # small queue: the first wall-clock grant after a compile-heavy
    # step can owe many requests at once, and everything admitted must
    # be drained below — cap the drain bill, the drill only needs >0
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=32,
                        block_size=8, max_queue=8)
    real = eng.submit(np.asarray([5, 6, 7], np.int32), 2,
                      tenant="steady")
    # flood accounting is wall-clock rps (the drill tracks real time,
    # not step count), so warm-compile runs can burn through a fixed
    # step budget before the first request is owed — step until the
    # flood lands, with a generous real-time ceiling
    reg = obs.get_registry()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        eng.step()
        if reg.counter("serve_tenant_requests_total").value(
                tenant="burst", state="queued") > 0:
            break
    chaos.reset()       # stop the flood, then drain what it queued
    eng.run_until_idle()
    assert real.state == "done"
    flooded = reg.counter("serve_tenant_requests_total").value(
        tenant="burst", state="queued")
    assert flooded > 0
    assert any(e["op"] == "tenant_flood"
               for e in flight.get_recorder().snapshot()
               if e["kind"] == "chaos")


# ---------------------------------------------------------------------------
# LoRA: per-request adapters vs the merged-weights oracle
# ---------------------------------------------------------------------------

def test_engine_lora_adapters_match_merged_weights(tiny_llama):
    """Adapter 0 is the base model exactly (zero-initialized B); every
    other adapter must reproduce, bit-for-bit, a sequential generate
    with that adapter's deltas folded into the q/v projection weights.
    Requests on different adapters share the batch and the prefix
    cache without contaminating each other."""
    model, params = tiny_llama
    # factors of N(0, 0.25): at the default 0.02 a rank-2 delta never
    # changes a greedy token of this model, and the test could not tell
    # an engine that applies an adapter from one that ignores it
    bank = init_lora_bank(model, num_adapters=3, rank=2,
                          rng=jax.random.PRNGKey(7), scale=0.25)
    prompt = (np.arange(1, 13) % (VOCAB - 1) + 1).astype(np.int32)
    n_new = 6
    refs = {0: _ref(model, params, prompt, n_new)}
    for adapter in (1, 2):
        refs[adapter] = _ref(model, merge_lora(params, bank, adapter),
                             prompt, n_new)
    # the witness can see an adapter: its merged weights move the answer
    assert any(not np.array_equal(refs[a], refs[0]) for a in (1, 2))
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=64,
                        block_size=8, lora_bank=bank)

    outs = {}
    for adapter in (0, 1, 2):
        r = eng.submit(prompt, n_new, adapter=adapter)
        eng.run_until_idle()
        assert r.state == "done", (r.state, r.reject_reason)
        outs[adapter] = np.asarray(r.tokens)

    for adapter in (0, 1, 2):
        np.testing.assert_array_equal(outs[adapter], refs[adapter])
    # and so does the engine: at least one diverges from base
    assert any(not np.array_equal(outs[a], outs[0]) for a in (1, 2))
    # same prompt, different adapter: the cache must NOT have crossed
    assert eng.prefix_cache.stats()["prefix_misses"] >= 3

    with pytest.raises(ValueError):
        eng.submit(prompt, 2, adapter=9)


@pytest.mark.slow  # ~3s: adapter-hit behavior; the oracle test above
#                    already covers lora correctness in tier-1
def test_engine_lora_same_adapter_repeat_hits_cache(tiny_llama):
    model, params = tiny_llama
    bank = init_lora_bank(model, num_adapters=2, rank=2,
                          rng=jax.random.PRNGKey(9))
    prompt = (np.arange(2, 14) % (VOCAB - 1) + 1).astype(np.int32)
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=64,
                        block_size=8, lora_bank=bank)
    a = eng.submit(prompt, 4, adapter=1)
    eng.run_until_idle()
    b = eng.submit(prompt, 4, adapter=1)
    eng.run_until_idle()
    np.testing.assert_array_equal(np.asarray(a.tokens),
                                  np.asarray(b.tokens))
    st = eng.prefix_cache.stats()
    assert st["prefix_hits"] == 1 and st["prefix_misses"] == 1


# ---------------------------------------------------------------------------
# Multi-tenant scheduling: quotas + DRR fairness
# ---------------------------------------------------------------------------

def _sched(num_blocks=16, block_size=4, **kw):
    return Scheduler(KVPool(num_blocks, block_size), **kw)


def test_tenant_quota_rejects_flood_not_neighbors():
    s = _sched(max_queue=64, tenant_quotas={"flood": 2})
    flood = [s.submit([1, 2], 2, tenant="flood") for _ in range(5)]
    light = s.submit([3, 4], 2, tenant="light")
    assert [r.state for r in flood[:2]] == ["queued", "queued"]
    assert all(r.state == "rejected"
               and r.reject_reason == "tenant_quota"
               for r in flood[2:])
    assert light.state == "queued"  # unquoted neighbor: untouched
    reg = obs.get_registry()
    c = reg.counter("serve_tenant_requests_total")
    assert c.value(tenant="flood", state="rejected") == 3
    assert c.value(tenant="flood", state="queued") == 2
    assert c.value(tenant="light", state="queued") == 1


def test_drr_rotation_prevents_tenant_starvation():
    """A tenant with a deep queue cannot monopolize admissions: the
    round-robin rotation gives the light tenant first claim on a
    subsequent pass."""
    s = _sched(num_blocks=64, max_prefills_per_round=2)
    flood = [s.submit([1, 2], 2, tenant="flood") for _ in range(6)]
    light = s.submit([9, 8], 2, tenant="light")
    first = s.next_admissions(free_slots=2)
    second = s.next_admissions(free_slots=2)
    admitted = [r.request_id for r in first + second]
    assert light.request_id in admitted, \
        "light tenant starved behind the flood"
    assert any(r.request_id in admitted for r in flood)


def test_engine_flood_cannot_starve_light_tenant(tiny_llama):
    """End-to-end starvation regression: with a quota on the flooding
    tenant, every light-tenant request completes, and the per-tenant
    admission counters prove both sides of the policy (light all done,
    flood rejected past its quota)."""
    model, params = tiny_llama
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=32,
                        block_size=8, max_queue=64,
                        tenant_quotas={"flood": 2})
    flood, rejected = [], 0
    for i in range(10):
        r = eng.submit(np.asarray([10 + i], np.int32), 2,
                       tenant="flood")
        rejected += r.state == "rejected"
        flood.append(r)
    light = [eng.submit(np.asarray([40 + i, 41], np.int32), 2,
                        tenant="light") for i in range(3)]
    eng.run_until_idle()
    assert all(r.state == "done" for r in light)
    reg = obs.get_registry()
    c = reg.counter("serve_tenant_requests_total")
    assert c.value(tenant="light", state="done") == 3
    assert c.value(tenant="flood", state="rejected") == rejected > 0
    # quota capped concurrent residency, not total service: early
    # flood requests that fit the quota still completed
    assert c.value(tenant="flood", state="done") >= 2


# ---------------------------------------------------------------------------
# Router prefix affinity
# ---------------------------------------------------------------------------

def test_router_prefers_replica_holding_the_prefix(tiny_llama):
    from types import SimpleNamespace

    model, params = tiny_llama
    mk = lambda: ServingEngine(model, params, max_slots=2,  # noqa: E731
                               max_seq_len=64, block_size=8)
    eng_a, eng_b = mk(), mk()
    prompt = (np.arange(3, 27) % (VOCAB - 1) + 1).astype(np.int32)
    r = eng_a.submit(prompt, 4)
    eng_a.run_until_idle()
    assert r.state == "done"

    router = Router()
    # B listed first: only the affinity term can flip the decision
    handles = [SimpleNamespace(state=READY, engine=eng_b),
               SimpleNamespace(state=READY, engine=eng_a)]
    repeat = np.concatenate([prompt, np.asarray([3, 4], np.int32)])
    assert router.place(handles, len(repeat) + 4) is handles[0]
    assert router.place(handles, len(repeat) + 4,
                        prompt=repeat) is handles[1]
    reg = obs.get_registry()
    assert reg.counter("serve_router_placements_total").value(
        outcome="placed") == 2


# ---------------------------------------------------------------------------
# Watchtower: the burn page names the burning tenant
# ---------------------------------------------------------------------------

def test_watchtower_burn_page_names_the_tenant():
    tower = Watchtower(WatchConfig(), dump_on_page=False)
    t = 1000.0
    # healthy default-tenant traffic keeps the GLOBAL window under the
    # page threshold while one tenant burns its budget completely
    for i in range(80):
        tower.observe({"ev": "serve_request", "t": t + i * 0.1,
                       "ok": True, "request_id": f"ok-{i}",
                       "tenant": "default", "ttft_s": 0.01})
    for i in range(12):
        tower.observe({"ev": "serve_request", "t": t + 8 + i * 0.1,
                       "ok": True, "request_id": f"slow-{i}",
                       "tenant": "acme", "ttft_s": 3.0})
    pages = [a for a in tower.alerts
             if a.kind == "slo_burn_rate" and a.severity == PAGE]
    assert len(pages) == 1
    assert pages[0].attribution.get("tenant") == "acme"
    assert "acme" in pages[0].detail
    assert "ttft:acme" in tower.summary()["burns_active"]
