"""Paged KV-cache pool and host-side block allocator (vLLM's PagedAttention
memory model, recast in tpu-mx's fixed-shape compile-cache idiom).

The device side is two preallocated arrays of shape ``(n_layers,
num_blocks, block_size, n_heads * d_head)`` — K and V — whose shapes never
change for the life of the engine, so every compiled program that touches
them keeps one signature regardless of how many requests come and go or
how long their sequences grow.  A request owns a *list of physical blocks*
(its block table); logical position ``p`` of a request lives at
``(table[p // block_size], p % block_size)``.  Block 0 is reserved as the
null/scratch block: padded prefill positions and inactive decode slots
write there, so the traced model step needs no branches.

The host side is :class:`BlockAllocator` — a plain free-list with a
high/low occupancy watermark pair.  The engine's default accounting is
*incremental* (vLLM's allocate-as-you-decode): admission takes only the
blocks the request's current context needs, every decode that crosses a
block boundary takes one more, and when the pool crosses the high
watermark — or a growth allocation fails outright — the engine preempts
victim requests (lowest priority, newest admitted first) back to the
waiting queue until occupancy falls to the low watermark, re-prefilling
their context through the chunked-prefill rungs on re-admission.  Steady-
state occupancy therefore tracks *actual* use, not the worst case.
``TPUMX_GEN_PREEMPTION=0`` restores the original reserve-ahead accounting
byte-for-byte (allocate ``ceil((prompt + max_new) / block_size)`` blocks
at admission, never preempt — an admitted request can never hit cache OOM
mid-decode, at the cost of pool headroom); both policies are documented
in docs/generation.md.

Speculative decoding (docs/generation.md "Speculative decoding") writes
ahead of the accepted context: a verify step scatters K/V for all s+1
fed positions, then the engine advances ``ctx_len`` only past the
accepted prefix.  Rejected entries need no device-side rollback in this
model — they live at positions >= the new context length, the causal
mask keeps them unread, and the next chunk fed at those positions
overwrites them.  What protects SHARED state is the same copy-on-write
machinery prefix caching uses: the engine CoWs the whole verify span
before dispatch, so a rejected write can never land in a block with
``refcount > 1`` (:meth:`PagedKVCache.snapshot_blocks` lets tests pin
this at the bit level).  The int8 pool has one extra wrinkle — a
partial rejection can requantize a mixed boundary block under a
transiently larger scale — handled engine-side by capping what the
prefix index may share (``_GenRequest.index_safe_len``).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

__all__ = ["BlockAllocator", "CacheKind", "PagedKVCache", "blocks_for",
           "ring_width", "window_blocks"]


def blocks_for(n_positions: int, block_size: int) -> int:
    """Number of cache blocks covering ``n_positions`` tokens."""
    return max(1, -(-int(n_positions) // int(block_size)))


def window_blocks(window: int, chunk: int, block_size: int) -> int:
    """The most blocks of a WINDOW kind one row ever owns while a step
    feeds it ``chunk`` positions: ``ceil((window + chunk) / block_size) +
    1`` — the ``window`` positions its first query still sees (one more
    while the step before is in flight), the chunk it writes, and the block
    both ends may straddle.  A window of 128 over blocks of 16 is 10 blocks
    at rest (``chunk`` 1) and 41 inside a 512-token chunk; one of 2,048
    over blocks of 32 is 66 and 81."""
    return -(-(int(window) + int(chunk)) // int(block_size)) + 1


def ring_width(window: int, chunk: int, block_size: int) -> int:
    """Columns of a window kind's table for a step that feeds ``chunk``
    positions a row: :func:`window_blocks` rounded up to a power of two.
    The table is a RING: logical block ``b`` sits in column ``b % width``."""
    return 1 << (window_blocks(window, chunk, block_size) - 1).bit_length()


class CacheKind:
    """One kind of a cache whose layers are not all of one kind
    (docs/generation.md "Cache kinds"): its ``name``, how many layers keep
    their state in it, the ``layout`` of its pools (``((name, minor
    width), ...)``), its own block count and :class:`BlockAllocator`, and
    ``window`` — 0 for a kind that keeps every position, else how many
    positions back a query still reads: a row of such a kind owns at most
    :func:`window_blocks` blocks whatever its length, its table is a RING
    (logical block ``b`` sits in column ``b % width``), and the kind is
    sized by slots where the others are sized by tokens: ``1 + rows x
    window_blocks(window, 1) + window_blocks(window, longest chunk)``
    blocks, HELD whatever the rows' lengths.  That is small where the
    window is (128 positions: 0.5 GB of ``mimo-v2.5``'s 13) and the
    largest thing in the cache where it is not (2,048 positions at 64
    slots: 3.4 GB, of which rows shorter than the window leave their part
    unused; docs/generation.md "Cache kinds").  ``span`` says
    which members of :attr:`PagedKVCache.pools` are this kind's.

    ``state`` marks a kind that is no pages at all but a slot's recurrent
    STATE (docs/generation.md "Cache kinds"): ``layout`` is then ``((name,
    shape), ...)``, a pool is ``(n_layers, num_blocks, *shape)``, and the
    one unit a row ever owns — whatever its length — is one index of the
    second axis, handed out by the same :class:`BlockAllocator` (index 0 is
    the scratch that idle rows point at).  Its "table" is one column wide
    and holds that index; nothing grows, nothing is freed behind a
    window, and ``num_blocks - 1`` is the number of slots.  Such a kind
    may be a cache's only one, or stand BEHIND a paged first kind, beside
    window kinds: it is sized by slots as they are, and a row then owns
    its one state unit and blocks of the paged kinds.

    ``writers`` / ``readers`` are the model's layers that write and read
    the kind, where its spec names them (else None): layer ``writers[j]``
    owns pool row ``j``, and a reader that is no writer — a cross layer
    over another layer's K and V — reads the row of the one writer
    (:meth:`pool_row`).  One writer and many readers is ``n_layers`` 1."""

    def __init__(self, name, n_layers, layout, num_blocks, window, span,
                 allocator=None, state=False, block_size=1, writers=None,
                 readers=None):
        self.name = str(name)
        self.n_layers = int(n_layers)
        self.writers = None if writers is None else tuple(writers)
        self.readers = None if readers is None else tuple(readers)
        if self.writers is not None and len(self.writers) != self.n_layers:
            raise ValueError(
                f"cache kind {name!r}: {len(self.writers)} writers for "
                f"{n_layers} pool rows (a layer that writes owns one row)")
        self.layout = layout
        self.num_blocks = int(num_blocks)
        self.window = int(window)
        self.span = span
        self.state = bool(state)
        self.block_size = int(block_size)
        self.allocator = allocator or BlockAllocator(self.num_blocks)

    def blocks_for(self, n_positions: int) -> int:
        """Units of this kind a row of ``n_positions`` owns: the blocks
        that cover them, or — a state kind — its one slot."""
        return 1 if self.state else blocks_for(n_positions, self.block_size)

    def pool_row(self, layer: int) -> int:
        """The row of this kind's pools that the model's ``layer`` reads:
        its own where it writes one, else the one writer's."""
        writers, readers = self.writers or (), self.readers or ()
        if layer in writers:
            return writers.index(layer)
        if layer in readers and len(writers) == 1:
            return 0
        raise ValueError(f"layer {layer} reads no row of cache kind "
                         f"{self.name!r}")


class BlockAllocator:
    """Free-list allocator over physical block ids ``1..num_blocks-1``
    (block 0 is the reserved null block).  Thread-safe; all-or-nothing
    allocation so a request is never half-admitted.

    Every allocated block carries a REFCOUNT (born 1 at :meth:`allocate`):
    :meth:`incref` marks sharing, :meth:`decref`/:meth:`free` release one
    reference and the block returns to the free list only at zero.  This
    is the bookkeeping prefix caching (ROADMAP item 3a, copy-on-write
    shared prompt blocks) needs, and what the int8 pool's per-block scale
    lifetime rides on today: a block's scales stay meaningful exactly as
    long as some owner holds a reference (docs/quantization.md).

    ``watermark_high`` / ``watermark_low`` are occupancy fractions the
    preempting engine steers by: crossing above high triggers victim
    preemption down to low (docs/generation.md "incremental allocation +
    preemption").  The allocator only reports them (:meth:`above_high`,
    :meth:`above_low`); the policy lives in the engine."""

    def __init__(self, num_blocks: int, watermark_high: float = 1.0,
                 watermark_low: float = 1.0):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        if not (0.0 < watermark_low <= watermark_high <= 1.0):
            raise ValueError(
                f"watermarks must satisfy 0 < low <= high <= 1, got "
                f"low={watermark_low}, high={watermark_high}")
        self.num_blocks = int(num_blocks)
        self.watermark_high = float(watermark_high)
        self.watermark_low = float(watermark_low)
        self._lock = threading.Lock()
        # pop() takes from the tail: hand out low ids first
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}  # block id -> live reference count

    def set_watermarks(self, high: float, low: float) -> None:
        if not (0.0 < low <= high <= 1.0):
            raise ValueError(
                f"watermarks must satisfy 0 < low <= high <= 1, got "
                f"low={low}, high={high}")
        self.watermark_high, self.watermark_low = float(high), float(low)

    def above_high(self) -> bool:
        """Occupancy strictly above the high watermark (preemption due)."""
        return self.occupancy() > self.watermark_high

    def above_low(self) -> bool:
        """Occupancy strictly above the low watermark (keep preempting)."""
        return self.occupancy() > self.watermark_low

    def blocks_above_low(self) -> int:
        """Blocks to free before :meth:`above_low` reads False: the count
        a loop freeing one block a turn under that test would free."""
        total = self.num_blocks - 1
        if not total:
            return 0
        used = self.num_used
        n = max(0, used - int(self.watermark_low * total))
        # settle on the division above_low() makes, digit for digit
        while n and (used - n + 1) / total <= self.watermark_low:
            n -= 1
        while (used - n) / total > self.watermark_low:
            n += 1
        return n

    @property
    def num_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def num_used(self) -> int:
        return (self.num_blocks - 1) - self.num_free

    def can_allocate(self, n: int) -> bool:
        return self.num_free >= int(n)

    def allocate(self, n: int) -> Optional[List[int]]:
        """``n`` blocks (refcount 1 each), or None (nothing taken) if
        fewer are free."""
        n = int(n)
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        with self._lock:
            if len(self._free) < n:
                return None
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._ref[b] = 1
        return out

    def incref(self, blocks: List[int]) -> None:
        """Add one reference to each allocated block (a sharer — e.g. a
        prefix-cache hit — now also holds it)."""
        with self._lock:
            for b in blocks:
                b = int(b)
                if b not in self._ref:
                    raise ValueError(
                        f"incref of unallocated block {b}")
            for b in blocks:
                self._ref[int(b)] += 1

    def decref(self, blocks: List[int]) -> List[int]:
        """Release one reference per block; blocks reaching zero return to
        the free list.  Returns the block ids actually freed."""
        freed: List[int] = []
        with self._lock:
            for b in blocks:
                b = int(b)
                if b <= 0 or b >= self.num_blocks:
                    raise ValueError(f"block id {b} out of range")
                if b not in self._ref:
                    raise ValueError(f"double free of block {b}")
            for b in blocks:
                b = int(b)
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    del self._ref[b]
                    self._free.append(b)
                    freed.append(b)
        return freed

    def refcount(self, block: int) -> int:
        """Live reference count of a block (0 = free)."""
        with self._lock:
            return self._ref.get(int(block), 0)

    @property
    def num_shared(self) -> int:
        """Blocks currently held by more than one owner — prefix-cache
        sharing (requests + the index) as opposed to exclusive request
        blocks; the occupancy gauges split on this (docs/generation.md
        "prefix caching")."""
        with self._lock:
            return sum(1 for c in self._ref.values() if c >= 2)

    def free(self, blocks: List[int]) -> None:
        """Release one reference per block (alias of :meth:`decref` —
        a block truly frees only when its LAST owner lets go)."""
        self.decref(blocks)

    def occupancy(self) -> float:
        """Fraction of allocatable blocks currently owned by requests."""
        total = self.num_blocks - 1
        return self.num_used / total if total else 0.0


class PagedKVCache:
    """The device-side pool: the arrays a model's ``cache_spec()`` asks
    for plus the allocator that parcels their blocks out to requests.

    A pool is ``(n_layers, num_blocks, block_size, width)``; ``pools``
    names them and gives their minor widths.  The default is the classic
    pair — ``(("k", n_heads * d_head), ("v", n_heads * d_head))`` — and a
    model with another kind of state says so: latent attention keeps ONE
    pool a layer, a token's normed latent and its rotated shared key side
    by side (``(("latent", kv_lora_rank + rope_dim),)``,
    docs/generation.md "Latent attention").  Allocator, prefix index,
    copy-on-write and preemption work on block ids and never look inside.

    A model whose layers are not all of one kind names ``kinds`` instead
    (docs/generation.md "Cache kinds"): ``({"name", "n_layers", "pools"},
    {"name", "n_layers", "pools", "window"}, ...)``.  The first keeps every
    position and is this cache as described above (``num_blocks`` sizes
    it, ``allocator`` is its allocator); each further one is a window kind
    (:class:`CacheKind`) with an allocator and a block count of its own,
    sized for ``window_rows = (rows, longest chunk)``.  ``pools`` stays ONE
    tuple, kind after kind (``kinds[i].span`` says which).  A spec that
    names no kinds has one, ``kinds[0]``, and is exactly today's cache.

    A spec whose ONLY kind is a state kind (``{"name", "n_layers", "state":
    ((pool name, shape), ...), "dtype"}``: a recurrent model, no position
    is kept) builds no paged pool and no allocator of token blocks: each
    pool is ``(n_layers, rows + 1, *shape)`` for ``window_rows = (rows,
    ...)``, ``allocator`` hands out the rows' indices (``num_blocks`` is
    ``rows + 1``, index 0 the scratch) and :meth:`blocks_for` is 1.

    A state kind may also stand BEHIND the first kind, beside window kinds
    (a model of state-space, window and full attention layers): the same
    pools and an allocator of its own over ``rows + 1`` indices, in its own
    ``dtype`` where it names one.  Every kind behind the first is sized by
    rows, so a free slot always has its units; the first alone is sized by
    tokens and admits under a watermark.  A kind may name the layers that
    write and read it (``writers`` / ``readers``): a kind one layer writes
    and several read has ONE pool row (:meth:`CacheKind.pool_row`).

    The arrays are owned functionally, as one tuple ``pools``: the engine
    threads it through its donated compiled programs and stores the
    returned (aliased) arrays back via :meth:`swap` — the pool is updated
    in place on device, and this object always points at the live copy
    (``k``, ``v``, ``k_scale``, ``v_scale`` read the classic pair's
    members).

    ``kv_dtype="int8"`` (docs/quantization.md) stores the classic pair
    QUANTIZED: K/V become int8 with symmetric per-``(layer, block, head)``
    scales in ``k_scale``/``v_scale`` (``(n_layers, num_blocks, n_heads)``
    f32, riding through the same donated programs).  The scatter path
    quantizes each chunk's K/V in-program and both attention paths
    dequantize at read — the pool then costs ~half the bf16 bytes, which
    is the ~2x block-budget headline (:meth:`num_blocks_for_bytes`).
    """

    def __init__(self, n_layers: Optional[int] = None,
                 n_heads: Optional[int] = None,
                 d_head: Optional[int] = None, num_blocks: int = 2,
                 block_size: int = 16, dtype=None,
                 kv_dtype: Optional[str] = None, pools=None, kinds=None,
                 window_rows=None):
        import jax.numpy as jnp

        if kinds is not None:
            if kv_dtype is not None or pools is not None:
                raise ValueError("a cache of several kinds names its pools "
                                 "kind by kind and is not quantized")
            if len(kinds) == 1 and "state" in kinds[0]:
                self._init_state(kinds[0], window_rows, block_size, dtype)
                return
            first, rest = kinds[0], kinds[1:]
            if first.get("window") or "state" in first or not all(
                    bool(k.get("window")) != ("state" in k) for k in rest):
                raise ValueError(
                    "the first cache kind keeps every position (it is what "
                    "num_blocks sizes) and every further kind is a window "
                    "kind or a slot's state, got " + str(
                        [(k["name"], k.get("window", 0), "state" in k)
                         for k in kinds]))
            n_layers, pools = first["n_layers"], first["pools"]

        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = jnp.dtype(dtype) if dtype is not None \
            else jnp.dtype(jnp.float32)
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        if kv_dtype == "int8" and pools is not None:
            raise ValueError("kv_dtype='int8' quantizes the K/V pair only; "
                             f"this cache holds {[n for n, _ in pools]}")
        self.kv_dtype = kv_dtype
        # heads folded into the minor dim: a block is a lane-dense
        # (block_size, H*D) tile, the layout the paged kernel's blocks
        # need on the chip (docs/pallas.md "block-layout rule")
        self.layout = _pool_layout(n_heads, d_head, pools)
        store = jnp.dtype(jnp.int8) if kv_dtype == "int8" else self.dtype
        # the device arrays as the ONE operand every program takes, donates
        # and returns: one per entry of the layout, then (int8 pool) the
        # two scale arrays
        self.pools = tuple(
            jnp.zeros((int(n_layers), self.num_blocks, self.block_size, w),
                      store) for _, w in self.layout)
        if kv_dtype == "int8":
            sshape = (int(n_layers), self.num_blocks, int(n_heads))
            # unwritten blocks carry scale 1: their (masked-out-of-
            # attention) garbage dequantizes to bounded values and the
            # first real write recomputes the scale from scratch
            self.pools += (jnp.ones(sshape, jnp.float32),
                           jnp.ones(sshape, jnp.float32))
        self.allocator = BlockAllocator(self.num_blocks)
        # the kinds: one for every cache of today's — these pools under
        # this allocator —, then a spec's window kinds, each with blocks
        # for ``window_rows = (rows, longest chunk)``: what every row owns
        # at rest and what the one row being prefilled owns besides
        first = kinds[0] if kinds else {"name": "kv"}
        self.kinds = (CacheKind(first["name"], n_layers, self.layout,
                                self.num_blocks, 0, slice(0, len(self.pools)),
                                self.allocator, block_size=self.block_size,
                                **_layers_of(first)),)
        for k in (kinds or ())[1:]:
            rows, chunk = window_rows
            at = len(self.pools)
            if "state" in k:
                self.kinds += (self._state_kind(k, rows, at, self.dtype),)
                continue
            layout = _pool_layout(None, None, k["pools"])
            n = 1 + rows * window_blocks(k["window"], 1, self.block_size) \
                + window_blocks(k["window"], chunk, self.block_size)
            self.pools += tuple(
                jnp.zeros((int(k["n_layers"]), n, self.block_size, w), store)
                for _, w in layout)
            self.kinds += (CacheKind(k["name"], k["n_layers"], layout, n,
                                     k["window"], slice(at, len(self.pools)),
                                     block_size=self.block_size,
                                     **_layers_of(k)),)

    def _state_kind(self, k, rows, at, dtype, allocator=None):
        """A kind that is a slot's state, its pools appended to
        :attr:`pools`: no pages, no token blocks — ``rows + 1`` states a
        layer (index 0 the scratch), their indices under an allocator."""
        import jax.numpy as jnp

        layout = tuple((str(n), tuple(int(d) for d in shape))
                       for n, shape in k["state"])
        dt = jnp.dtype(k.get("dtype") or dtype or jnp.float32)
        self.pools += tuple(
            jnp.zeros((int(k["n_layers"]), int(rows) + 1) + shape, dt)
            for _, shape in layout)
        return CacheKind(k["name"], k["n_layers"], layout, int(rows) + 1, 0,
                         slice(at, len(self.pools)), allocator, state=True,
                         **_layers_of(k))

    def _init_state(self, k, window_rows, block_size, dtype):
        """The cache of a spec whose ONE kind is a slot's state: that
        kind's pools alone, the slots' indices under :attr:`allocator`."""
        import jax.numpy as jnp

        self.kv_dtype = None
        self.block_size = int(block_size)
        self.dtype = jnp.dtype(dtype if dtype is not None else jnp.float32)
        self.num_blocks = int(window_rows[0]) + 1
        self.allocator = BlockAllocator(self.num_blocks)
        self.pools = ()
        kind = self._state_kind(k, window_rows[0], 0, self.dtype,
                                self.allocator)
        self.layout = kind.layout
        self.kinds = (kind,)

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    @property
    def k(self):
        return self.pools[0]

    @property
    def v(self):
        return self.pools[1]

    @property
    def k_scale(self):
        return self.pools[2] if self.quantized else None

    @property
    def v_scale(self):
        return self.pools[3] if self.quantized else None

    @property
    def shape(self):
        return tuple(self.pools[0].shape)

    def blocks_for(self, n_positions: int) -> int:
        """Units of the first kind (the one under :attr:`allocator`) that a
        row of ``n_positions`` owns."""
        return self.kinds[0].blocks_for(n_positions)

    def max_positions(self) -> int:
        """Positions one request could address if it owned every block."""
        return (self.num_blocks - 1) * self.block_size

    def swap(self, pools) -> None:
        """Adopt the pool arrays returned by a donated program call."""
        self.pools = tuple(pools)

    def snapshot_blocks(self, blocks: List[int]) -> Dict[str, "object"]:
        """Device-bit snapshot of the given physical blocks — every pool
        under its name (``k`` and ``v``, or what the model's spec named)
        and, int8 pool, ``k_scale`` / ``v_scale`` — as host numpy arrays.
        Test/debug helper for the speculative-decoding rollback guarantee
        (docs/generation.md "Speculative decoding"): shared prefix
        blocks must be bit-identical before and after a verify step
        that rejected drafts, because rejected writes only ever land in
        the writer's PRIVATE (copy-on-write) tail blocks."""
        import numpy as np

        idx = np.asarray([int(b) for b in blocks], np.int32)
        names = [n for n, _ in self.layout]
        if self.quantized:
            names += ["k_scale", "v_scale"]
        # (of the first kind: a window kind's blocks are another
        # allocator's numbers)
        return {n: np.asarray(p[:, idx]) for n, p in zip(names, self.pools)}

    def nbytes(self) -> int:
        return sum(int(p.nbytes) for p in self.pools)

    @staticmethod
    def bytes_per_block(n_layers: int, n_heads: Optional[int] = None,
                       d_head: Optional[int] = None, block_size: int = 16,
                       dtype=None, kv_dtype: Optional[str] = None,
                       pools=None) -> int:
        """Device bytes one pool block costs (every pool + scales), by the
        arguments of the constructor."""
        import jax.numpy as jnp

        item = 1 if kv_dtype == "int8" else \
            jnp.dtype(dtype if dtype is not None else jnp.float32).itemsize
        per = n_layers * block_size * item * sum(
            w for _, w in _pool_layout(n_heads, d_head, pools))
        if kv_dtype == "int8":
            per += 2 * n_layers * n_heads * 4  # f32 k/v scales
        return per

    @classmethod
    def num_blocks_for_bytes(cls, pool_bytes: int, *spec, **spec_kw) -> int:
        """How many blocks a byte budget buys, the spec given as to
        :meth:`bytes_per_block` — the density comparison: at identical
        ``pool_bytes`` the int8 pool's budget is ~2x the bf16 one (scales
        cost ``8/(block_size*d_head)`` of the win)."""
        return int(pool_bytes) // cls.bytes_per_block(*spec, **spec_kw)


def _layers_of(k) -> dict:
    """The layers a kind's spec says write and read it, for
    :class:`CacheKind`."""
    return {n: k[n] for n in ("writers", "readers") if n in k}


def _pool_layout(n_heads, d_head, pools):
    """``((name, minor width), ...)`` of a cache: what the spec names, or
    the classic K/V pair of folded heads."""
    if pools is None:
        width = int(n_heads) * int(d_head)
        return (("k", width), ("v", width))
    return tuple((str(n), int(w)) for n, w in pools)
