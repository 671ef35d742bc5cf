"""counts_afmoe.py against hand-worked numbers at ``trinity-mini``'s widths."""
from perfbench import counts_afmoe as ca


def test_a_cached_token_costs_what_the_issue_reckons():
    # 4 KV heads x (128 + 128) x 2 B = 2,048 B a layer, either kind
    assert ca.kind_read_bytes(1, 4, 128, 128, 1) == 2048
    # 4 full layers: 8 KB a token; 12 window layers: 24 KB a position held
    assert ca.kind_read_bytes(1, 4, 128, 128, 4) == 8192
    assert ca.kind_read_bytes(1, 4, 128, 128, 12) == 24576
    # a row past the window reads 2,048 positions in 12 layers: 50 MB a step
    assert ca.kind_read_bytes(2048, 4, 128, 128, 12) == 50331648


def test_read_bytes_of_a_full_batch_s_decode_step():
    # 64 rows of ~3.9 k cached positions: 2.0 GB over the 4 full layers
    assert ca.kind_read_bytes(64 * 3900, 4, 128, 128, 4) == 2044723200
    # the window layers, were every row past the window: 3.2 GB
    assert ca.kind_read_bytes(64 * 2048, 4, 128, 128, 12) == 3221225472


def test_flops_of_attention():
    # 32 heads x (128 score + 128 value) x 2 a pair a layer
    assert ca.kind_flops(1, 32, 128, 128, 1) == 16384
    # the decode body is bound by bytes: 8 operations a byte, under the
    # ridge of 240
    assert ca.kind_flops(1, 32, 128, 128, 1) \
        / ca.kind_read_bytes(1, 4, 128, 128, 1) == 8


def test_prefill_reads_a_tile_s_share():
    # a tile of 256 queries of one of 8 query heads a KV head reads its
    # context once: a pair costs 8 / 256 of a position
    assert ca.prefill_read_bytes(3200, 256 / 8, 4, 128, 128, 4) \
        == ca.kind_read_bytes(3200, 4, 128, 128, 4) // 32 == 819200


def test_held_expert_work():
    # an expert is 3 x 2048 x 1024 values: 12.6 MB, 12.6 M operations a token
    assert ca.held_expert_bytes(1, 2048, 1024) == 3 * 2048 * 1024 * 2
    assert ca.held_expert_flops(1, 2048, 1024) == 6 * 2048 * 1024
    # 16 held x 14 layers touched in a step: 2.8 GB of a step's bytes
    assert ca.held_expert_bytes(16 * 14, 2048, 1024) == 2818572288


def test_the_window_pool():
    from mxnet_tpu.serving.generation.kv_cache import window_blocks

    # held for 64 slots at their worst and a 512-token chunk: 4,306 blocks
    held = 1 + 64 * window_blocks(2048, 1, 32) + window_blocks(2048, 512, 32)
    assert held == 4306
    assert ca.pool_used_pct(2150, held - 1) == 100.0 * 2150 / 4305
    assert ca.pool_used_pct(0, 0) is None
    # a block is 12 layers x 32 positions x 2,048 B = 786,432 B: 3.39 GB
    assert held * ca.kind_read_bytes(32, 4, 128, 128, 12) == 3386376192


def test_the_trips_are_the_kernel_s_own():
    """What ``afm.window_trips_per_row`` divides, from the geometry the
    tiles body takes at the cell's shapes: 16 pages a trip (65 pages of 64 KB double-buffered
    are 8.5 MB, over the 6 MB a trip may hold), 5 trips a row past the
    window (4 where its window begins on a page's first position: one
    position in 32), 1 for a row of 300 positions."""
    import numpy as np

    from mxnet_tpu.ops import paged_attention as pa

    assert pa._tile_pages(1, 32, 128, 2048, 2 * 32 * 512 * 2) == 16
    pool = np.zeros((1, 2, 32, 512), np.float16)
    pos = np.asarray([[5000], [300], [2047], [2048 + 31]], np.int32)
    trips = pa.tiles_decode_trips(pos, pos[:, 0], pool, pool, 128, groups=8,
                                  window=2048)
    assert int(trips) == 5 + 1 + 4 + 4
