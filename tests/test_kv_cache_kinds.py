"""``PagedKVCache`` built from a spec of several kinds, with no model behind
it: a full kind, a window kind and a slot's state side by side
(docs/generation.md "three kinds in one cache")."""
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.serving.generation.kv_cache import (PagedKVCache, ring_width,
                                                   window_blocks)

KV = (("k", 32), ("v", 32))
ROWS, CHUNK, BS = 6, 16, 4


def _spec(**state_more):
    return dict(dtype=jnp.bfloat16, kinds=(
        dict(name="full", n_layers=1, pools=KV, writers=(5,),
             readers=(5, 7, 9)),
        dict(name="window", n_layers=2, pools=KV, window=8, writers=(1, 3),
             readers=(1, 3)),
        dict(name="state", n_layers=3, dtype=jnp.float32,
             state=(("ssm", (24, 128)),), writers=(0, 2, 4),
             readers=(0, 2, 4), **state_more)))


@pytest.fixture()
def cache():
    return PagedKVCache(num_blocks=40, block_size=BS,
                        window_rows=(ROWS, CHUNK), **_spec())


def test_the_three_kinds_sizes(cache):
    full, window, state = cache.kinds
    assert cache.num_blocks == full.num_blocks == 40
    assert window.num_blocks == 1 + ROWS * window_blocks(8, 1, BS) \
        + window_blocks(8, CHUNK, BS) == 1 + 6 * 4 + 7
    assert state.num_blocks == ROWS + 1
    assert [tuple(p.shape) for p in cache.pools] == [
        (1, 40, BS, 32), (1, 40, BS, 32), (2, 32, BS, 32), (2, 32, BS, 32),
        (3, ROWS + 1, 24, 128)]
    # pages in the spec's dtype, the state in its own
    assert [str(p.dtype) for p in cache.pools] == ["bfloat16"] * 4 \
        + ["float32"]
    assert cache.nbytes() == sum(int(p.nbytes) for p in cache.pools)
    assert ring_width(8, 1, BS) == 4 and ring_width(8, CHUNK, BS) == 8


def test_a_row_owns_one_state_unit_and_blocks_of_the_paged_kinds(cache):
    full, window, state = cache.kinds
    assert (full.blocks_for(17), window.blocks_for(17),
            state.blocks_for(17), state.blocks_for(17000)) == (5, 5, 1, 1)
    assert cache.blocks_for(17) == 5          # the first kind's
    assert cache.allocator is full.allocator
    assert len({id(k.allocator) for k in cache.kinds}) == 3


def test_both_admissions_and_release_kind_by_kind(cache):
    """Slots run out while blocks are free, and blocks while slots are:
    each kind's allocator says so alone, and gives back alone."""
    full, window, state = (k.allocator for k in cache.kinds)
    slots = [state.allocate(1) for _ in range(ROWS)]
    assert all(slots) and state.allocate(1) is None
    assert sorted(s[0] for s in slots) == list(range(1, ROWS + 1))
    assert full.can_allocate(39) and window.can_allocate(31)
    state.free(slots[2])
    assert state.allocate(1) == slots[2]      # the freed unit, first again
    blocks = full.allocate(39)
    assert full.allocate(1) is None and state.num_used == ROWS
    full.free(blocks)
    for s in slots:
        state.free(s)
    assert (full.num_used, window.num_used, state.num_used) == (0, 0, 0)


def test_a_readers_pool_row_is_the_writers(cache):
    full, window, state = cache.kinds
    assert full.n_layers == 1 and full.writers == (5,)
    assert {full.pool_row(layer) for layer in full.readers} == {0}
    assert [window.pool_row(layer) for layer in window.readers] == [0, 1]
    assert [state.pool_row(layer) for layer in state.readers] == [0, 1, 2]
    for layer in (0, 6, 8):
        with pytest.raises(ValueError, match="reads no row"):
            full.pool_row(layer)


def test_a_spec_of_one_or_two_kinds_builds_what_it_built():
    two = PagedKVCache(num_blocks=40, block_size=BS,
                       window_rows=(ROWS, CHUNK), dtype=jnp.float32,
                       kinds=_spec()["kinds"][:2])
    assert [k.name for k in two.kinds] == ["full", "window"]
    assert len(two.pools) == 4 and not any(k.state for k in two.kinds)
    state = dict(_spec()["kinds"][2])
    one = PagedKVCache(block_size=BS, window_rows=(ROWS, CHUNK),
                       kinds=(state,))
    (kind,) = one.kinds
    assert kind.state and one.allocator is kind.allocator
    assert one.num_blocks == ROWS + 1 and one.blocks_for(900) == 1
    assert [tuple(p.shape) for p in one.pools] == [(3, ROWS + 1, 24, 128)]
    plain = PagedKVCache(2, 4, 8, num_blocks=16, block_size=BS)
    assert [k.name for k in plain.kinds] == ["kv"]
    assert plain.kinds[0].writers is None and plain.kinds[0].readers is None


@pytest.mark.parametrize("kinds,message", [
    ((2, 0), "first cache kind keeps every"),
    ((1, 0), "first cache kind keeps every"),
    ((0, 0), "window kind or a slot's state"),
], ids=["state-first", "window-first", "two-full"])
def test_what_a_spec_may_not_combine(kinds, message):
    spec = _spec()["kinds"]
    with pytest.raises(ValueError, match=message):
        PagedKVCache(num_blocks=40, block_size=BS, window_rows=(ROWS, CHUNK),
                     kinds=tuple(spec[i] for i in kinds))


def test_a_kind_cannot_be_a_window_and_a_state():
    both = dict(_spec()["kinds"][2], window=8)
    with pytest.raises(ValueError, match="window kind or a slot's state"):
        PagedKVCache(num_blocks=40, block_size=BS, window_rows=(ROWS, CHUNK),
                     kinds=(_spec()["kinds"][0], both))


def test_the_state_pools_start_at_zero(cache):
    assert not np.asarray(cache.pools[4]).any()


# -- a window as wide as trinity-mini's: 2,048 positions over blocks of 32 ------
WIDE, WBS, WROWS, WCHUNK = 2048, 32, 64, 512


def test_a_wide_windows_blocks_ring_and_pool():
    """What a row owns at most: the 2,048 positions its next query sees
    are 64 blocks, 65 where they straddle, one more while the step before
    is in flight — 66 at rest, 81 inside a 512-token chunk; every table is
    a ring of 128 columns; and the kind HOLDS 1 + 64 x 66 + 81 = 4,306
    blocks whatever the rows' lengths (3.39 GB at 12 layers of 2 KB a
    position) where a window of 128 over blocks of 16 holds 1,322."""
    assert window_blocks(WIDE, 1, WBS) == 66
    assert window_blocks(WIDE, 128, WBS) == 69
    assert window_blocks(WIDE, WCHUNK, WBS) == 81
    assert {ring_width(WIDE, t, WBS) for t in (1, 128, WCHUNK)} == {128}
    assert 1 + 128 * window_blocks(128, 1, 16) + window_blocks(128, 512, 16) \
        == 1322
    cache = PagedKVCache(num_blocks=8, block_size=WBS, dtype=jnp.bfloat16,
                         window_rows=(WROWS, WCHUNK), kinds=(
        dict(name="full", n_layers=1, pools=(("k", 8), ("v", 8))),
        dict(name="window", n_layers=2, pools=(("k", 8), ("v", 8)),
             window=WIDE)))
    window = cache.kinds[1]
    assert window.num_blocks == 4306 and window.window == WIDE
    assert [tuple(p.shape) for p in cache.pools[window.span]] == \
        [(2, 4306, WBS, 8)] * 2
    # at the cell's widths a block is 12 x 32 x (512 + 512) x 2 B
    assert window.num_blocks * 12 * WBS * 1024 * 2 == 3386376192


def test_a_wide_window_slides_while_its_row_decodes():
    """The engine's side (``_slide``, ``_ring_tables``), no program run: a
    row prefilled to 300 positions decodes to 5,000.  Under the window it
    owns its length's blocks; past it 64 or 65 (66 is the bound: one more
    while the step before is in flight and the host is a position
    behind); every block that slid out went back to the kind; the ring of
    128 columns never holds two of its blocks in one column, and holds
    each at its logical block modulo 128."""
    import jax

    from mxnet_tpu.parallel import hybrid_moe as hm
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    cfg = hm.HybridMoeConfig(
        vocab_size=31, hidden_size=16, intermediate_size=16,
        moe_intermediate_size=8, num_hidden_layers=2,
        hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1),
        num_attention_heads=2, num_key_value_heads=1, head_dim=8,
        v_head_dim=8, swa_num_attention_heads=2, swa_num_key_value_heads=1,
        swa_head_dim=8, swa_v_head_dim=8, sliding_window=WIDE,
        partial_rotary_factor=1.0, n_routed_experts=2, num_experts_per_tok=1,
        max_position_embeddings=8192)
    model = hm.HybridMoeLM(cfg, max_len=8192, kv_dtype=jnp.float32)
    svc = GenerationService(
        hm.hybrid_moe_init(cfg, jax.random.PRNGKey(0)), model,
        GenerationConfig(max_slots=2, block_size=WBS, num_blocks=300,
                         seq_buckets=[128, 512], prefix_cache=False),
        start=False)
    kind = svc._cache.kinds[1]
    assert kind.num_blocks == 1 + 2 * 66 + 81

    class Row:
        rid, wins = 0, None
    row = Row()
    svc._slide(row, 0, 300)
    svc._slide(row, 300, 300)
    assert len(row.wins[0][1]) == 10 == kind.allocator.num_used
    most = 0
    for pos in range(300, 5000):
        svc._slide(row, pos, pos + 1)
        first, blocks = row.wins[0]
        most = max(most, len(blocks))
        assert len(blocks) == kind.allocator.num_used
        if pos < WIDE:
            assert (first, len(blocks)) == (0, pos // WBS + 1)
        elif pos >= WIDE + WBS:
            assert len(blocks) in (64, 65)
            # the first position the query at ``pos`` reads is held
            assert first * WBS <= pos - (WIDE - 1) < (first + 1) * WBS
        if pos % 257 == 0:
            (table,) = svc._ring_tables([(1, row)], 2, 1)
            assert table.shape == (2, 128) and not table[0].any()
            cols = np.nonzero(table[1])[0]
            assert len(cols) == len(blocks)
            for j, b in enumerate(blocks):
                assert table[1, (first + j) % 128] == b
    assert most == 65 < window_blocks(WIDE, 1, WBS)
    assert svc.stats()["counts"]["window_blocks_freed"] \
        == 5000 // WBS + 1 - len(row.wins[0][1])
    svc._drop_windows(row)
    assert kind.allocator.num_used == 0
    svc.stop(drain=False, timeout=30)


# -- granite-4.0-h-micro: a state kind LARGER than the paged kind beside it ---
G_SLOTS = 64


def _matrix_state_cache(num_blocks, slots, n_attn=4, n_mamba=36):
    """The published widths with the paged pools' lanes and the blocks cut
    (nothing of this size is made on the test's CPU but the spec)."""
    from mxnet_tpu.ops.ssd import state_shapes

    return dict(num_blocks=num_blocks, block_size=32, dtype=jnp.bfloat16,
                window_rows=(slots, 1024), kinds=(
        dict(name="full", n_layers=n_attn, pools=(("k", 512), ("v", 512)),
             writers=(5, 15, 25, 35)[:n_attn],
             readers=(5, 15, 25, 35)[:n_attn]),
        dict(name="state", n_layers=n_mamba, dtype=jnp.float32,
             state=state_shapes(64, 64, 128, 4),
             writers=tuple(range(n_mamba)), readers=tuple(range(n_mamba)))))


@pytest.mark.parametrize("what", ["bytes a slot", "slot 0 the scratch",
                                  "larger than the paged kind"])
def test_a_matrix_state_kind_behind_a_paged_kind(what):
    """Mamba-2's kind: ONE pool a layer of (128 + 8) x 4,096 float32 — a
    head's matrix state, the convolution's 3 x 4,352 inputs in the last
    sublane tile —, sized by slots, index 0 the scratch idle rows point at;
    and the LARGEST thing in a cache that also has a paged kind: 80.2 MB a
    slot where a token's K and V are 8,192 B."""
    if what == "bytes a slot":
        from mxnet_tpu.ops.ssd import state_shapes

        ((name, shape),) = state_shapes(64, 64, 128, 4)
        assert (name, shape) == ("ssd", (136, 4096))
        assert 36 * 136 * 4096 * 4 == 80216064       # as stored
        assert 36 * (64 * 64 * 128 + 3 * 4352) * 4 == 77377536
        return
    # two Mamba-2 layers and two slots of the published widths: 13 MB
    cache = PagedKVCache(**_matrix_state_cache(8, 2, n_attn=1, n_mamba=2))
    full, state = cache.kinds
    assert (full.state, state.state) == (False, True)
    assert [tuple(p.shape) for p in cache.pools] == [
        (1, 8, 32, 512), (1, 8, 32, 512), (2, 3, 136, 4096)]
    assert [str(p.dtype) for p in cache.pools] == ["bfloat16"] * 2 \
        + ["float32"]
    if what == "slot 0 the scratch":
        assert state.num_blocks == 3 and state.allocator.num_free == 2
        got = [state.allocator.allocate(1)[0] for _ in range(2)]
        assert sorted(got) == [1, 2] and state.allocator.allocate(1) is None
        assert state.blocks_for(1) == state.blocks_for(32768) == 1
        assert state.allocator is not cache.allocator
        return
    # at the cell's sizes (64 slots, 8,192 blocks of 32): 5.21 GB of state
    # beside 2.15 GB of K and V — slots are what admission runs out of
    slot = int(cache.pools[2].nbytes) // state.num_blocks // 2 * 36
    block = sum(int(p.nbytes) for p in cache.pools[:2]) // full.num_blocks * 4
    assert (slot, block) == (80216064, 32 * 8192)
    assert (G_SLOTS + 1) * slot == 5214044160 > 8192 * block == 2147483648
    assert slot // (block // 32) == 9792     # a slot is ~9.8 k tokens of K/V
