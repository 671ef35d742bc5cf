"""``counts_sambay.py`` against the arithmetic ISSUE 44 and PERF.md give."""
from perfbench import counts_sambay as cs


def test_a_slots_state_by_the_mathematics():
    # 9 layers x (16 + 3) x 5120 float32: "3.5 MB a slot"
    assert cs.state_bytes_per_slot(5120, 16, 4, 9) == 3502080
    assert cs.ssm_decode_bytes(128, 5120, 16, 4, 9) == 2 * 128 * 3502080
    # a chunk moves its row's state once each way and every position's
    # step, input, output, B and C
    assert cs.scan_prefill_bytes(1, 512, 5120, 16, 4, 9) == \
        2 * 3502080 + 512 * (3 * 5120 + 32) * 4 * 9
    assert cs.ssm_flops(1, 5120, 16, 9) == 9 * 5120 * 16 * 7


def test_the_shared_pool_is_read_once_a_reader():
    # ONE layer's K and V: 5,120 B a token a reader
    assert cs.kv_read_bytes(1, 20, 64, 1) == 5120
    # 128 rows x ~3 k positions x 8 readers: "about 16 GB of the step"
    assert cs.kv_read_bytes(128 * 3072, 20, 64, 8) == 128 * 3072 * 5120 * 8
    assert 15.5e9 < cs.kv_read_bytes(128 * 3072, 20, 64, 8) < 16.5e9
    # 8 window layers x 512 positions x 128 rows: 2.7 GB
    assert cs.kv_read_bytes(128 * 512, 20, 64, 8) == 128 * 512 * 40960
    # a pair costs a query head a score over 64 and a sum over 128
    assert cs.diff_attn_flops(1, 40, 64, 1) == 40 * 2 * 192
    assert cs.prefill_read_bytes(512, 64, 20, 64, 8) == 512 * 40960 // 64


def test_the_steps_bytes_are_the_issues_arithmetic():
    total, parts = cs.decode_step_bytes(
        128, 128 * 3072, 128 * 512, 7.7e9, 20, 64, 8, 8, 5120, 16, 4, 9)
    assert 26e9 < total < 28e9
    assert 0.55 < parts["full"] / total < 0.60
    assert 0.70 < (parts["full"] + parts["window"] + parts["state"]) \
        / total < 0.74


def test_cache_bytes_a_token_over_three_kinds():
    # 96 blocks of 32 x 5,120 B, 18 window blocks of 32 x 40,960 B, one
    # state, over 3,072 live tokens
    got = cs.cache_bytes_per_token(
        [96, 18, 1], [32 * 5120, 32 * 40960, 4423680], 3072)
    assert round(got) == round((96 * 163840 + 18 * 1310720 + 4423680) / 3072)
    assert cs.cache_bytes_per_token([1, 1, 1], [1, 1, 1], 0) is None
