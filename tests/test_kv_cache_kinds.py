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
