"""Prefix caching (mxnet_tpu.serving.generation.prefix_cache,
docs/generation.md "prefix caching"): chained-hash index semantics,
hit-vs-miss greedy bit-identity across pool dtypes, copy-on-write
isolation of shared blocks, LRU eviction under watermark pressure ahead
of preemption, preemption-decref + resume re-hit, the suffix-charging
overload estimator, zero post-warmup recompiles under freeze, router
shared-prefix affinity, and TPUMX_GEN_PREFIX_CACHE=0 byte-identity.
"""
import time

import jax
import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving.generation import (BlockAllocator, GenerationConfig,
                                          GenerationService,
                                          PrefixCacheIndex, blocks_for)
from mxnet_tpu.serving.generation.prefix_cache import ROOT_KEY, chain_hash

pytestmark = pytest.mark.prefix

CFG = tr.TransformerConfig(vocab=40, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64)


@pytest.fixture(autouse=True)
def _fresh_observability():
    yield
    obs.recompile.reset()


@pytest.fixture(scope="module")
def params():
    return tr.transformer_lm_init(CFG, jax.random.PRNGKey(0))


def _gc(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("seq_buckets", [16, 32])
    kw.setdefault("max_new_tokens", 8)
    return GenerationConfig(**kw)


# -- the chained-hash index ---------------------------------------------------------
def test_chain_hash_commits_to_full_prefix():
    """A chunk key depends on every token before it: equal keys iff the
    whole prefix is token-for-token identical."""
    a = np.arange(16)
    b = np.arange(16)
    k1 = chain_hash(ROOT_KEY, a[:8])
    k2 = chain_hash(ROOT_KEY, b[:8])
    assert k1 == k2
    assert chain_hash(k1, a[8:]) == chain_hash(k2, b[8:])
    # same second block, different first block -> different chain key
    c = np.concatenate([np.arange(8)[::-1], np.arange(8, 16)])
    kc = chain_hash(chain_hash(ROOT_KEY, c[:8]), c[8:])
    assert kc != chain_hash(k1, a[8:])


def test_index_match_insert_refcount_semantics():
    alloc = BlockAllocator(16)
    idx = PrefixCacheIndex(alloc, block_size=4)
    toks = np.arange(11)  # 2 full blocks + a 3-token tail
    owned = alloc.allocate(3)
    assert idx.insert(toks, owned) == 2          # tail block never indexed
    assert idx.num_blocks == 2
    assert alloc.refcount(owned[0]) == 2         # request + cache
    # longest-prefix match: full prompt, a prefix, and a diverging prompt
    got, n = idx.acquire(toks)
    assert got == owned[:2] and n == 8
    assert alloc.refcount(owned[0]) == 3
    alloc.decref(got)
    got, n = idx.acquire(toks[:7])               # only 1 full block covered
    assert got == owned[:1] and n == 4
    alloc.decref(got)
    div = toks.copy()
    div[1] = 39                                  # first block differs
    assert idx.acquire(div) == ([], 0)
    # sub-block prompts can never match
    assert idx.peek(toks[:3]) == 0
    # owner releases; blocks stay RESIDENT on the cache's own reference
    alloc.free(owned)
    assert alloc.refcount(owned[0]) == 1
    assert idx.peek(toks) == 8
    # duplicate content never double-indexes
    dup = alloc.allocate(2)
    assert idx.insert(toks[:8], dup) == 0
    alloc.free(dup)


def test_index_lru_evicts_cache_only_leaves_first():
    alloc = BlockAllocator(16)
    idx = PrefixCacheIndex(alloc, block_size=4)
    a = alloc.allocate(2)
    idx.insert(np.arange(8), a)
    b = alloc.allocate(2)
    idx.insert(np.arange(8, 16), b)
    alloc.free(a)
    # chain a is cache-only; chain b's blocks are still request-held
    idx.acquire(np.arange(8, 16))  # touch b: a is now also the LRU side
    alloc.decref(b)                # drop the acquire refs again
    freed = idx.evict_blocks(4)
    # a's LEAF (block a[1]) must go before its parent, and b (request-held)
    # must not be evicted at all
    assert freed == 2
    assert alloc.refcount(a[1]) == 0 and alloc.refcount(a[0]) == 0
    assert idx.num_blocks == 2 and idx.peek(np.arange(8, 16)) == 8
    alloc.free(b)


def test_index_capacity_cap_is_honored():
    alloc = BlockAllocator(32)
    idx = PrefixCacheIndex(alloc, block_size=4, capacity_blocks=3)
    a = alloc.allocate(2)
    idx.insert(np.arange(8), a)
    alloc.free(a)
    b = alloc.allocate(2)
    idx.insert(np.arange(8, 16), b)
    alloc.free(b)
    assert idx.num_blocks <= 3
    assert idx.evictions >= 1


def test_allocator_num_shared():
    a = BlockAllocator(8)
    blocks = a.allocate(3)
    assert a.num_shared == 0
    a.incref(blocks[:2])
    assert a.num_shared == 2
    a.decref(blocks[:2])
    assert a.num_shared == 0
    a.free(blocks)


# -- eviction: the one walk against the walk a victim --------------------------------
def _walk_victim(entries, refcount, protect=()):
    """The walk the index made for EVERY victim before it found a call's
    victims in one (prefix_cache.py before PR 41, as it stood): the least
    recently used cache-only leaf, the first strict minimum in dict order."""
    victim = None
    for e in entries.values():
        if e.children or e.key in protect:
            continue
        if refcount(e.block) != 1:
            continue  # some request still reads it: evicting frees nothing
        if victim is None or e.tick < victim.tick:
            victim = e
    return victim


class _WalkAVictim(PrefixCacheIndex):
    """The oracle: the same index, its victims found a walk apiece."""

    def _evict_locked(self, n, protect=()):
        freed = 0
        while freed < n:
            victim = _walk_victim(self._entries, self._alloc.refcount,
                                  protect)
            if victim is None:
                break
            del self._entries[victim.key]
            if victim.parent is not None:
                victim.parent.children -= 1
            self._alloc.decref([victim.block])
            self.evictions += 1
            freed += 1
        return freed


class _Pair:
    """An index and the oracle over allocators of their own, fed one
    history: block ids, free lists and entries must never part."""

    def __init__(self, num_blocks, capacity):
        self.allocs = [BlockAllocator(num_blocks) for _ in range(2)]
        self.idx = [cls(a, block_size=4, capacity_blocks=capacity)
                    for cls, a in zip((PrefixCacheIndex, _WalkAVictim),
                                      self.allocs)]

    def both(self, fn):
        got, want = [fn(i, a) for i, a in zip(self.idx, self.allocs)]
        assert got == want
        return got

    def check(self):
        new, old = self.idx
        # the victims and their ORDER: a freed block goes to the tail
        assert self.allocs[0]._free == self.allocs[1]._free
        assert self.allocs[0]._ref == self.allocs[1]._ref
        assert new.evictions == old.evictions
        assert list(new._entries) == list(old._entries)
        assert [(e.block, e.tick, e.children) for e in new._entries.values()] \
            == [(e.block, e.tick, e.children) for e in old._entries.values()]


def _documents(rs, n=6):
    """Token sequences that share prefixes of whole blocks: a tree with
    forks, so an eviction can make a parent the next victim."""
    docs = [rs.randint(0, 50, 40)]
    for _ in range(n - 1):
        base = docs[rs.randint(len(docs))]
        cut = 4 * rs.randint(0, 6)
        docs.append(np.concatenate([base[:cut], rs.randint(50, 99, 40 - cut)]))
    return docs


@pytest.mark.parametrize("capacity", [0, 12])
@pytest.mark.parametrize("seed", range(6))
def test_one_walk_evicts_what_a_walk_a_victim_evicted(seed, capacity):
    """Random histories of insert / acquire / decref / free /
    evict_blocks(n): the blocks evicted, their order, ``evictions`` and the
    surviving keys equal the old walk's at every step."""
    rs = np.random.RandomState(seed)
    pair = _Pair(48, capacity)
    docs = _documents(rs)
    held = []          # block lists some "request" still references
    within_call = skipped_held = ties = 0
    for _ in range(400):
        op = rs.choice(["serve", "serve", "touch", "release", "release",
                        "evict", "coarsen"])
        if op == "serve":
            # a request: share what is cached, allocate the rest, index it
            toks = docs[rs.randint(len(docs))][:rs.randint(4, 41)]
            shared = pair.both(lambda i, a: i.acquire(toks))[0]
            need = blocks_for(len(toks), 4) - len(shared)
            fresh = pair.both(lambda i, a: a.allocate(need))
            if fresh is None:
                pair.both(lambda i, a: a.decref(shared))
            else:
                pair.both(lambda i, a: i.insert(toks, shared + fresh))
                held.append(shared + fresh)
        elif op == "touch":
            toks = docs[rs.randint(len(docs))]
            blocks = pair.both(lambda i, a: i.acquire(toks))[0]
            if blocks:
                held.append(blocks)
        elif op == "release" and held:
            blocks = held.pop(rs.randint(len(held)))
            if rs.randint(2):
                pair.both(lambda i, a: a.free(blocks))
            else:
                pair.both(lambda i, a: a.decref(blocks))
        elif op == "coarsen":
            # equal ticks on leaves of different chains (a monotone map
            # keeps a parent no older than its child)
            for i in pair.idx:
                for e in i._entries.values():
                    e.tick -= e.tick % 4
        elif op == "evict":
            new, old = pair.idx
            leaves = [e for e in old._entries.values() if not e.children]
            ticks = [e.tick for e in leaves]
            ties += len(set(ticks)) < len(ticks)
            skipped_held += any(
                pair.allocs[1].refcount(e.block) > 1 for e in leaves)
            inner = {k for k, e in old._entries.items() if e.children}
            n = int(rs.randint(1, old.num_reclaimable() + 3))
            walks = new.evict_walks
            freed = pair.both(lambda i, a: i.evict_blocks(n))
            assert freed <= n and new.evict_walks == walks + 1
            # a leaf's eviction made its parent a victim of the same call
            within_call += bool(inner - set(old._entries))
        pair.check()
    # the histories reach the cases the equality is claimed for
    assert within_call and skipped_held and ties
    for blocks in held:
        pair.both(lambda i, a: a.free(blocks))
    left = pair.idx[0].num_blocks
    assert pair.both(lambda i, a: i.evict_blocks(left + 5)) == left
    pair.check()
    assert not pair.idx[0]._entries and not pair.allocs[0]._ref


def test_a_crossings_cost_is_one_walk():
    """``k`` evictions over an index of ``m`` entries visit the entries
    once and then a parent a victim: no clock needed."""
    alloc = BlockAllocator(512)
    idx = PrefixCacheIndex(alloc, block_size=4)
    rs = np.random.RandomState(0)
    for _ in range(40):                      # 40 chains of 10 blocks
        blocks = alloc.allocate(10)
        idx.insert(rs.randint(0, 1000, 40), blocks)
        alloc.free(blocks)
    m, k = idx.num_blocks, 150
    assert m == 400
    assert idx.evict_blocks(k) == k
    st = idx.stats()
    assert st["evictions"] == k and st["evict_walks"] == 1
    assert m <= st["evict_scanned"] <= m + k
    # a call that finds nothing walks once and frees nothing; one that
    # asks for nothing does not walk
    held = alloc.allocate(4)
    lone = PrefixCacheIndex(alloc, block_size=4)
    lone.insert(np.arange(16), held)
    assert lone.evict_blocks(3) == 0 and lone.evict_walks == 1
    assert lone.evict_blocks(0) == 0 and lone.evict_walks == 1


# -- hit-vs-miss bit-identity -------------------------------------------------------
@pytest.mark.parametrize("variant", ["f32", "bf16", "int8"])
def test_hit_vs_miss_greedy_bit_identity(params, variant):
    """Acceptance: greedy tokens are bit-identical whether the prompt
    prefilled from scratch or reused shared blocks — f32, bf16 and int8
    pools (the int8 scales are shared and copied with the block)."""
    kw = {}
    if variant == "bf16":
        kw["amp_dtype"] = "bfloat16"
    if variant == "int8":
        kw["kv_dtype"] = "int8"
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, CFG.vocab, 24),   # block-aligned: full hit
               rs.randint(0, CFG.vocab, 27)]   # partial tail: suffix hit

    def run(prefix_cache):
        svc = GenerationService(params, CFG,
                                _gc(prefix_cache=prefix_cache, **kw),
                                start=False)
        svc.start()  # no warmup: programs compile on demand, fewer total
        outs = [[svc.generate(p, timeout=180) for p in prompts]
                for _ in range(2)]   # second pass hits
        stats = svc.stats()
        svc.stop()
        return outs, stats

    (first, second), st = run(True)
    (base, base2), st_off = run(False)
    assert first == second == base == base2
    assert st["prefix_cache"]["hits"] >= 2
    assert st["prefix_cache"]["cached_tokens"] >= 24 + 24
    assert st_off["prefix_cache"] is None
    # the cached pass computed a fraction of the prefill positions
    assert st["prefix_cache"]["prefill_tokens"] \
        < st_off["counts"]["prefill_tokens"]


def test_cow_isolation_shared_blocks_never_mutated(params):
    """Acceptance: a writer appending past a fully-cached prompt gets a
    private copy-on-write block — the index's shared bits are bitwise
    untouched, and a later sharer decodes identically."""
    svc = GenerationService(params, CFG, _gc(prefix_cache=True),
                            start=False)
    svc.start()
    prompt = np.random.RandomState(3).randint(0, CFG.vocab, 24)
    a = svc.generate(prompt, timeout=180)
    # snapshot the indexed blocks' device bits before the writer runs
    shared = sorted(e.block for e in svc._prefix._entries.values())
    assert shared, "finished request must leave its full blocks resident"
    k_before = np.asarray(svc._cache.k)[:, shared].copy()
    v_before = np.asarray(svc._cache.v)[:, shared].copy()
    b = svc.generate(prompt, timeout=180)   # full hit -> CoW -> appends
    stats = svc.stats()
    assert stats["prefix_cache"]["cow_copies"] >= 1
    np.testing.assert_array_equal(k_before,
                                  np.asarray(svc._cache.k)[:, shared])
    np.testing.assert_array_equal(v_before,
                                  np.asarray(svc._cache.v)[:, shared])
    c = svc.generate(prompt, timeout=180)   # sharer after the append
    svc.stop()
    assert a == b == c


# -- eviction / preemption interplay ------------------------------------------------
def test_lru_eviction_under_watermark_pressure(params):
    """A stream of distinct prompts through a tight pool: the cache
    yields LRU blocks instead of wedging admission, everything
    completes, and evictions are counted."""
    svc = GenerationService(params, CFG,
                            _gc(num_blocks=12, preemption=True,
                                prefix_cache=True),
                            start=False)
    svc.start()
    rs = np.random.RandomState(5)
    for i in range(6):
        out = svc.generate(rs.randint(0, CFG.vocab, 24),
                           max_new_tokens=4, timeout=180)
        assert len(out) == 4
    stats = svc.stats()
    svc.stop()
    assert stats["counts"]["finished"] == 6
    assert stats["prefix_cache"]["evictions"] >= 1
    # the pool itself never exceeded its bound (sanity)
    assert stats["kv_blocks"]["used"] <= stats["kv_blocks"]["total"]


def _copy_of(index, alloc):
    """The oracle over a copy of an index's and its allocator's state."""
    twin = BlockAllocator(alloc.num_blocks, alloc.watermark_high,
                          alloc.watermark_low)
    twin._free, twin._ref = list(alloc._free), dict(alloc._ref)
    out = _WalkAVictim(twin, index.block_size)
    for e in index._entries.values():
        c = out._entries[e.key] = type(e)(
            e.key, e.block, e.parent and out._entries[e.parent.key],
            e.tick, e.seq)
        c.children = e.children
    return out, twin


def test_a_crossing_is_one_walk_and_the_loops_victims(params):
    """Rows that grow push the pool over ``watermark_high``: the index
    evicts down to ``watermark_low`` in ONE walk, and frees the blocks, in
    the order, that ``evict_blocks(1)`` in a loop frees on a copy."""
    svc = GenerationService(params, CFG,
                            _gc(num_blocks=41, max_new_tokens=48,
                                preemption=True, prefix_cache=True),
                            start=False)
    alloc, index = svc._cache.allocator, svc._prefix
    inner, crossings = svc._watermark_preempt_locked, []

    def crossing():
        if not alloc.above_high():
            return inner()
        oracle, twin = _copy_of(index, alloc)
        while twin.above_low() and oracle.evict_blocks(1):
            pass
        n_free, walks = len(alloc._free), index.evict_walks
        inner()
        crossings.append((alloc._free[n_free:], twin._free[n_free:],
                          alloc._ref == twin._ref,
                          index.evict_walks - walks, alloc.above_low()))

    svc._watermark_preempt_locked = crossing
    svc.start()
    rs = np.random.RandomState(5)
    try:
        for h in [svc.submit(rs.randint(0, CFG.vocab, 10), max_new_tokens=40)
                  for _ in range(12)]:
            assert len(h.result(180)) == 40
        stats = svc.stats()
    finally:
        svc.stop()
    assert len(crossings) >= 3
    for freed, want, same_in_use, walks, still_above in crossings:
        assert freed == want and len(freed) >= 7
        assert same_in_use and walks == 1 and not still_above
    pc = stats["prefix_cache"]
    assert stats["counts"]["preempted"] == 0
    assert pc["evictions"] >= sum(len(c[0]) for c in crossings)
    assert pc["evict_walks"] >= len(crossings)
    # a walk visits at most the pool's 40 blocks, then a parent a victim
    assert pc["evict_scanned"] <= pc["evict_walks"] * 40 + pc["evictions"]


def test_preemption_decref_and_resume_rehit(params):
    """Preempting a request holding shared blocks decrefs (the cache keeps
    them resident) and its re-prefill re-hits the index — and the whole
    run stays bit-identical to prefix_cache=0."""
    def run(prefix_cache):
        svc = GenerationService(params, CFG,
                                _gc(max_slots=2, num_blocks=8,
                                    preemption=True,
                                    prefix_cache=prefix_cache),
                                start=False)
        rs = np.random.RandomState(1)
        hs = [svc.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12)
              for _ in range(2)]
        svc.start()
        outs = [h.result(180) for h in hs]
        evs = [h.stats() for h in hs]
        stats = svc.stats()
        svc.stop()
        return outs, evs, stats

    outs, evs, stats = run(True)
    outs_off, _, stats_off = run(False)
    assert outs == outs_off
    assert stats["counts"]["preempted"] >= 1
    assert stats_off["counts"]["preempted"] >= 1
    # the resumed request's re-prefill served tokens from the cache
    assert stats["prefix_cache"]["hits"] >= 1
    assert stats["prefix_cache"]["cached_tokens"] >= 8
    resumed = [ev for ev in evs if ev["preemptions"] >= 1]
    assert resumed and resumed[0]["prefix_cached_tokens"] >= 8
    assert "prefix_reuse" in resumed[0]["breakdown_ms"]


# -- overload estimator -------------------------------------------------------------
def test_admission_estimator_charges_uncached_suffix(params):
    """The projected-block budget charges only the uncached suffix (plus
    CoW slack) once the prefix index can serve the rest."""
    svc = GenerationService(params, CFG, _gc(prefix_cache=True),
                            start=False)
    svc.start()
    prompt = np.random.RandomState(9).randint(0, CFG.vocab, 24)
    svc.generate(prompt, max_new_tokens=8, timeout=180)
    h = svc.submit(prompt, max_new_tokens=8)
    # worst case is blocks_for(24 + 8, 8) = 4; the index holds 3 full
    # blocks, so the charge is 4 - 3 + 1 (CoW slack) = 2
    assert blocks_for(24 + 8, 8) == 4
    assert h._req.charged_blocks == 2
    h.result(180)
    svc.stop()


# -- program discipline -------------------------------------------------------------
def test_zero_postwarmup_recompiles_with_prefix_cache(params, monkeypatch):
    """Acceptance: warmup enumerates the cache-hit suffix rungs, the
    fully-cached 1-token recompute, and the CoW copy — full hits,
    suffix hits and resume re-hits then run under TPUMX_FREEZE_COMPILES=1
    with 1 miss per signature."""
    svc = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=16,
                                preemption=True, prefix_cache=True),
                            start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(11)
    aligned = rs.randint(0, CFG.vocab, 24)
    ragged = rs.randint(0, CFG.vocab, 29)
    svc.start()
    for _ in range(2):  # second pass: full hit (CoW) + suffix hit
        assert len(svc.generate(aligned, max_new_tokens=4,
                                timeout=180)) == 4
        assert len(svc.generate(ragged, max_new_tokens=4,
                                timeout=180)) == 4
    stats = svc.compile_stats()
    pc = svc.stats()["prefix_cache"]
    svc.stop()
    assert pc["hits"] >= 2 and pc["cow_copies"] >= 1
    assert any(k[0] == "gen_block_copy" for k in stats)
    assert all(v["misses"] == 1 for v in stats.values())


def test_prefix_cache_off_is_byte_identical(params, monkeypatch):
    """Acceptance: TPUMX_GEN_PREFIX_CACHE=0 restores today's behavior —
    no index, no CoW program, no prefix program keys, and bitwise
    identical tokens."""
    monkeypatch.setenv("TPUMX_GEN_PREFIX_CACHE", "0")
    cfg = _gc()
    assert cfg.prefix_cache is False
    monkeypatch.delenv("TPUMX_GEN_PREFIX_CACHE")
    svc = GenerationService(params, CFG, cfg, start=False)
    svc.warmup()
    svc.start()
    prompt = np.random.RandomState(13).randint(0, CFG.vocab, 24)
    offs = [svc.generate(prompt, timeout=180) for _ in range(2)]
    stats = svc.stats()
    cstats = svc.compile_stats()
    svc.stop()
    assert svc._prefix is None
    assert stats["prefix_cache"] is None
    assert all(k[0] != "gen_block_copy" for k in cstats)
    # the off-service's program-key set is exactly the pre-cache
    # enumeration: every key is a gen_prefill/gen_decode signature
    assert {k[0] for k in cstats} <= {"gen_prefill", "gen_decode"}
    svc_on = GenerationService(params, CFG, _gc(prefix_cache=True),
                               start=False)
    svc_on.warmup()
    svc_on.start()
    ons = [svc_on.generate(prompt, timeout=180) for _ in range(2)]
    on_keys = set(svc_on.compile_stats())
    svc_on.stop()
    assert offs == ons
    # cache-off keys are a strict subset: the cache only ADDS programs
    # (the copy + extra suffix rungs), never changes existing ones
    assert set(cstats) < on_keys


# -- router affinity ----------------------------------------------------------------
def test_router_shared_prefix_affinity(params):
    """Same-prefix requests ride to the replica that last served that
    prefix, concentrating cache hits on one engine; health gating is
    unchanged."""
    from mxnet_tpu.serving.router import GenerationRouter, RouterConfig

    router = GenerationRouter(
        params, CFG, gen_config=_gc(prefix_cache=True, max_new_tokens=4),
        config=RouterConfig(num_replicas=2, affinity=True))
    rs = np.random.RandomState(2)
    shared = rs.randint(0, CFG.vocab, 16)
    prompts = [np.concatenate([shared, rs.randint(0, CFG.vocab, 4)])
               for _ in range(5)]
    # the first request runs alone, so its prefix blocks are indexed
    # before the other four are admitted: whether a request submitted in
    # the same instant hits or misses is a thread race, not the subject
    hs = [router.submit(prompts[0], max_new_tokens=4)]
    assert len(hs[0].result(180)) == 4
    hs += [router.submit(p, max_new_tokens=4) for p in prompts[1:]]
    for h in hs:
        assert len(h.result(180)) == 4
    replicas = {h.replica for h in hs}
    hits = [rep.service.stats()["prefix_cache"]["hits"]
            for rep in router._replicas]
    st = router.stats()
    router.stop()
    assert len(replicas) == 1, "affinity must pin the shared prefix"
    assert max(hits) >= 4 and min(hits) == 0
    assert st["affinity"] is True and st["affinity_entries"] >= 1


def test_router_affinity_off_still_serves(params):
    from mxnet_tpu.serving.router import GenerationRouter, RouterConfig

    router = GenerationRouter(
        params, CFG, gen_config=_gc(prefix_cache=True, max_new_tokens=3),
        config=RouterConfig(num_replicas=2, affinity=False))
    rs = np.random.RandomState(4)
    prompt = rs.randint(0, CFG.vocab, 20)
    outs = [router.generate(prompt, max_new_tokens=3, timeout=180)
            for _ in range(4)]
    st = router.stats()
    router.stop()
    assert all(o == outs[0] for o in outs)
    assert st["affinity"] is False and st["affinity_entries"] == 0


# -- wide-event partition stays exact ----------------------------------------------
def test_prefix_reuse_segment_keeps_partition_exact(params):
    """The prefix_reuse slice joins the lifetime partition without
    breaking its exactness: components still sum to TTFT / total."""
    svc = GenerationService(params, CFG, _gc(prefix_cache=True),
                            start=False)
    svc.start()
    prompt = np.random.RandomState(6).randint(0, CFG.vocab, 24)
    svc.generate(prompt, timeout=180)
    h = svc.submit(prompt, max_new_tokens=4)
    h.result(180)
    ev = h.stats()
    svc.stop()
    assert ev["prefix_cached_tokens"] >= 24
    assert "prefix_reuse" in ev["breakdown_ms"]
    assert sum(ev["ttft_breakdown_ms"].values()) == \
        pytest.approx(ev["ttft_ms"], abs=0.05)
    assert sum(ev["breakdown_ms"].values()) == \
        pytest.approx(ev["total_ms"], abs=0.05)
