"""counts_hybrid.py against hand-worked numbers at ``mimo-v2.5``'s widths."""
from perfbench import counts_hybrid as ch


def test_a_cached_token_costs_what_the_issue_reckons():
    # full: 4 KV heads x (192 + 128) x 2 B = 2,560 B a layer, 2 layers;
    # window: 8 KV heads, 5,120 B a layer, 5 layers
    assert ch.kind_read_bytes(1, 4, 192, 128, 1) == 2560
    assert ch.kind_read_bytes(1, 8, 192, 128, 1) == 5120
    assert ch.kind_read_bytes(1, 4, 192, 128, 2) == 5120
    assert ch.kind_read_bytes(1, 4, 192, 128, 2) \
        + ch.kind_read_bytes(1, 8, 192, 128, 5) == 30720


def test_read_bytes_of_a_full_decode_step():
    # 128 rows of 4,700 cached positions: 3.1 GB over the 2 full layers;
    # the window layers read 128 positions a row whatever its length
    assert ch.kind_read_bytes(128 * 4700, 4, 192, 128, 2) == 3080192000
    assert ch.kind_read_bytes(128 * 128, 8, 192, 128, 5) == 419430400


def test_flops_of_attention():
    # 64 heads x (192 score + 128 value) x 2 a pair a layer
    assert ch.kind_flops(1, 64, 192, 128, 1) == 40960
    # the decode body is bound by bytes: 40,960 / 2,560 = 16 operations a
    # byte in the full kind, 8 in the window kind, under the ridge of 240
    assert ch.kind_flops(1, 64, 192, 128, 1) \
        / ch.kind_read_bytes(1, 8, 192, 128, 1) == 8


def test_prefill_reads_a_tile_s_share():
    # a tile of 256 queries of one of 16 query heads a KV head reads its
    # context once: a pair costs 16 / 256 of a position
    assert ch.prefill_read_bytes(1600, 256 / 16, 4, 192, 128, 2) \
        == ch.kind_read_bytes(1600, 4, 192, 128, 2) // 16 == 512000


def test_cache_bytes_per_token():
    # a block of 16 positions: 2 layers x 2,560 B (full), 5 x 5,120 (window)
    full, window = 16 * 5120, 16 * 25600
    # 128 rows of 4,700 tokens: 294 full blocks and 10 window blocks a row
    per = ch.cache_bytes_per_token([128 * 294, 128 * 10], [full, window],
                                   128 * 4700)
    assert 5900 < per < 6100
    # every layer keeping every token: 30,720 B
    assert ch.cache_bytes_per_token([294, 294], [full, window], 294 * 16) \
        == 30720
    assert ch.cache_bytes_per_token([0, 0], [full, window], 0) is None


def test_held_expert_work_is_counts_mla_s():
    assert ch.held_expert_bytes(96, 4096, 2048) == 96 * 3 * 4096 * 2048 * 2
    assert ch.held_expert_flops(1, 4096, 2048) == 6 * 4096 * 2048
