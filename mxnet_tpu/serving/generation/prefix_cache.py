"""Prefix-cache index: longest-prefix reuse of resident KV blocks.

This is the host-side bookkeeping for ROADMAP item 3(a) — PagedAttention
block sharing (vLLM, Kwon et al. 2023) extended with radix-style
longest-prefix matching (SGLang RadixAttention): prompt tokens are hashed
per block-sized chunk with a CHAINED hash, so a chunk's key commits to its
entire prefix — two prompts share a cache entry iff they are token-for-
token identical up to and including that block.  The index maps chain keys
to physical blocks of the :class:`~.kv_cache.PagedKVCache` pool that
already hold those tokens' K/V, holding ONE allocator reference per
indexed block (the "cache-only" reference): a block stays resident after
its last request finishes, ready for the next admission to ``incref`` and
reuse, and truly frees only when the index evicts it.

Sharing discipline (docs/generation.md "prefix caching"):

- only FULL blocks are ever indexed — a partially-written tail block is
  still being appended to by its owner and can never be shared;
- indexed blocks are read-only to sharers: the engine copy-on-writes any
  block with ``refcount > 1`` before scattering into it
  (``GenerationPrograms.copy_block``), so writers never touch shared
  history;
- eviction is LRU over CACHE-ONLY leaves (refcount held solely by the
  index, no indexed children): evicting an interior entry would orphan
  its descendants, and evicting a block some request still holds frees no
  memory — the engine runs eviction ahead of victim preemption when the
  allocator crosses its watermarks.  A call for ``n`` victims walks the
  entries ONCE (the eligible leaves into a heap by recency, a parent
  entering it when its last child goes), so a crossing that frees 15% of
  the pool costs one walk and not one a block.

The index never touches the device: matching, insertion, and eviction are
pure host arithmetic + refcount bookkeeping, and a cache hit reuses the
EXISTING chunked-prefill program ladder (no new program shapes).

Speculative decoding (docs/generation.md "Speculative decoding")
composes safely with all of the above: :meth:`PrefixCacheIndex.insert`
only ever indexes FULL blocks of the ACCEPTED context the engine hands
it, and rejected speculative writes land exclusively at positions past
that context in the writer's private (copy-on-write) tail blocks — so a
shared or indexed block can never hold a rejected draft's K/V.  For the
int8 pool the engine additionally caps the insert length at the
request's ``index_safe_len`` (a partial-rejection verify can requantize
a mixed boundary block under a transiently larger scale, and such a
block must not be shared).
"""
from __future__ import annotations

import hashlib
import heapq
import threading
from typing import Dict, List, Optional, Tuple

import numpy as _np

__all__ = ["PrefixCacheIndex", "chain_hash", "ROOT_KEY"]

#: the chain-hash seed: the key of the empty prefix
ROOT_KEY = b"tpumx-prefix-root"


def chain_hash(prev: bytes, chunk) -> bytes:
    """Key of one block-sized token chunk, chained on its prefix's key —
    ``H(prev || tokens)`` — so equal keys imply equal full prefixes
    (up to blake2b collisions, 128-bit)."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(_np.ascontiguousarray(
        _np.asarray(chunk, dtype=_np.int32)).tobytes())
    return h.digest()


class _Entry:
    __slots__ = ("key", "block", "parent", "children", "tick", "seq")

    def __init__(self, key: bytes, block: int, parent: Optional["_Entry"],
                 tick: int, seq: int):
        self.key = key
        self.block = block
        self.parent = parent
        self.children = 0  # indexed child entries (chain continuation)
        self.tick = tick   # LRU recency
        self.seq = seq     # insertion number: the order among equal ticks


class PrefixCacheIndex:
    """Chain-keyed longest-prefix index over resident pool blocks.

    Parameters
    ----------
    allocator : :class:`~.kv_cache.BlockAllocator`
        The pool's allocator — the index holds one reference per indexed
        block and releases it at eviction.
    block_size : int
        Tokens per block (the chunk size of the chain hash).
    capacity_blocks : int
        Cap on indexed blocks (the ``TPUMX_GEN_PREFIX_CACHE_BLOCKS``
        reserve); 0 = bounded only by the pool and watermark eviction.
    """

    def __init__(self, allocator, block_size: int,
                 capacity_blocks: int = 0):
        if int(block_size) < 1:
            raise ValueError("block_size must be >= 1")
        self._alloc = allocator
        self._bs = int(block_size)
        self._cap = max(0, int(capacity_blocks))
        self._lock = threading.Lock()
        self._entries: Dict[bytes, _Entry] = {}
        self._tick = 0
        self.evictions = 0   # cumulative blocks dropped from the index
        self.insertions = 0  # cumulative blocks indexed
        self.evict_walks = 0    # cumulative walks over the entries
        self.evict_scanned = 0  # cumulative entries those walks visited

    # -- introspection ------------------------------------------------------------
    @property
    def block_size(self) -> int:
        return self._bs

    @property
    def num_blocks(self) -> int:
        """Blocks currently indexed (each holds one cache reference)."""
        with self._lock:
            return len(self._entries)

    def num_reclaimable(self) -> int:
        """Upper bound on blocks eviction could return to the free list
        right now or after its leaves go first: every indexed block whose
        only reference is the cache's own."""
        with self._lock:
            return sum(1 for e in self._entries.values()
                       if self._alloc.refcount(e.block) == 1)

    def stats(self) -> dict:
        with self._lock:
            return {"blocks": len(self._entries),
                    "capacity": self._cap,
                    "insertions": self.insertions,
                    "evictions": self.evictions,
                    "evict_walks": self.evict_walks,
                    "evict_scanned": self.evict_scanned}

    # -- the chain walk -----------------------------------------------------------
    def _walk(self, tokens) -> List[bytes]:
        """Chain keys of every FULL block of ``tokens``, in prefix order."""
        toks = _np.asarray(tokens)
        out: List[bytes] = []
        key = ROOT_KEY
        for i in range(len(toks) // self._bs):
            key = chain_hash(key, toks[i * self._bs:(i + 1) * self._bs])
            out.append(key)
        return out

    def peek(self, tokens) -> int:
        """Tokens the index would serve for this prompt (longest cached
        full-block prefix), WITHOUT taking references or touching LRU —
        the admission estimator's probe."""
        keys = self._walk(tokens)
        n = 0
        with self._lock:
            for k in keys:
                if k not in self._entries:
                    break
                n += 1
        return n * self._bs

    def acquire(self, tokens) -> Tuple[List[int], int]:
        """Longest cached prefix match for ``tokens``: returns the shared
        physical blocks (one reference taken on each, so they cannot be
        freed under the caller) and the token count they cover.  Touches
        the matched chain's LRU recency."""
        keys = self._walk(tokens)
        blocks: List[int] = []
        with self._lock:
            self._tick += 1
            for k in keys:
                e = self._entries.get(k)
                if e is None:
                    break
                e.tick = self._tick
                blocks.append(e.block)
            if blocks:
                self._alloc.incref(blocks)
        return blocks, len(blocks) * self._bs

    def insert(self, tokens, blocks: List[int]) -> int:
        """Index every full block of ``tokens`` not yet present, taking
        one cache reference per newly indexed block.  ``blocks[i]`` must
        hold the K/V of tokens ``[i*bs, (i+1)*bs)``.  A chain key that
        already exists keeps its existing block (identical content —
        equal chained keys mean equal token prefixes), so concurrent
        identical prefills never double-index.  Returns the number of
        blocks newly indexed; stops early if the capacity cap cannot be
        honored by evicting elsewhere."""
        toks = _np.asarray(tokens)
        n_full = min(len(toks) // self._bs, len(blocks))
        if n_full <= 0:
            return 0
        added = 0
        with self._lock:
            self._tick += 1
            key = ROOT_KEY
            parent: Optional[_Entry] = None
            protect = set()
            for i in range(n_full):
                key = chain_hash(key, toks[i * self._bs:(i + 1) * self._bs])
                e = self._entries.get(key)
                if e is None:
                    if self._cap and len(self._entries) >= self._cap:
                        # make room, never by sawing off our own chain
                        if not self._evict_locked(1, protect):
                            break
                    b = int(blocks[i])
                    if self._alloc.refcount(b) < 1:
                        break  # caller raced a release; stop cleanly
                    self._alloc.incref([b])
                    e = _Entry(key, b, parent, self._tick, self.insertions)
                    self._entries[key] = e
                    if parent is not None:
                        parent.children += 1
                    self.insertions += 1
                    added += 1
                else:
                    e.tick = self._tick
                protect.add(key)
                parent = e
        return added

    # -- eviction -----------------------------------------------------------------
    def _evict_locked(self, n: int, protect=()) -> int:
        """Drop up to ``n`` CACHE-ONLY leaves (refcount 1 — only the index
        holds it — no indexed children, not in ``protect``), least
        recently used first and the earlier inserted of equal ticks first;
        their blocks return to the free list.  ONE walk over the entries
        finds every eligible leaf; a victim that was its parent's last
        child makes the parent the next candidate (never older than the
        child: ``acquire`` and ``insert`` touch a chain from its root).
        Returns the number dropped."""
        if n <= 0:
            return 0
        refcount = self._alloc.refcount
        # a block some request still reads is skipped: evicting frees nothing
        heap = [(e.tick, e.seq, e) for e in self._entries.values()
                if not e.children and e.key not in protect
                and refcount(e.block) == 1]
        heapq.heapify(heap)
        self.evict_walks += 1
        self.evict_scanned += len(self._entries)
        blocks = []
        while len(blocks) < n and heap:
            victim = heapq.heappop(heap)[2]
            del self._entries[victim.key]
            blocks.append(victim.block)
            up = victim.parent
            if up is not None:
                up.children -= 1
                self.evict_scanned += 1
                if not up.children and up.key not in protect \
                        and refcount(up.block) == 1:
                    heapq.heappush(heap, (up.tick, up.seq, up))
        self._alloc.decref(blocks)   # to the free list in the victims' order
        self.evictions += len(blocks)
        return len(blocks)

    def evict_blocks(self, n: int) -> int:
        """Evict up to ``n`` cache-only leaves LRU-first (the watermark /
        allocation-pressure path — runs AHEAD of victim preemption) in one
        walk over the entries.  Returns the number of blocks actually
        freed."""
        with self._lock:
            return self._evict_locked(int(n))

    def drop_all(self) -> int:
        """Release every cache reference and clear the index (service
        shutdown hygiene).  Blocks still shared with live requests simply
        lose the cache's reference."""
        with self._lock:
            n = len(self._entries)
            for e in self._entries.values():
                self._alloc.decref([e.block])
            self._entries.clear()
        return n
