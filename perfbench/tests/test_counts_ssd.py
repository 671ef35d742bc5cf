"""``counts_ssd.py`` against the arithmetic ISSUE 51 and PERF.md give."""
from perfbench import counts_ssd as cs

STATE = (64, 64, 128, 4, 36)    # heads, head size, entries, taps, layers


def test_a_slots_state_by_the_mathematics():
    # 36 layers x (64 x 64 x 128 + 3 x 4352) float32: "77.4 MB a slot"
    assert cs.state_bytes_per_slot(*STATE) == 77377536
    # the matrix states alone: "75.5 MB", 18 times phi-4-mini-flash's 4.4
    assert 36 * 64 * 64 * 128 * 4 == 75497472
    assert cs.ssd_decode_bytes(64, *STATE) == 2 * 64 * 77377536
    # a chunk moves its row's state once each way and every position's
    # input and output a lane, B, C and the heads' steps
    assert cs.scan_prefill_bytes(1, 1024, *STATE) == \
        2 * 77377536 + 1024 * (2 * 4096 + 256 + 64) * 4 * 36
    assert cs.ssd_flops(1, 64, 64, 128, 36) == 36 * 64 * 64 * 128 * 5


def test_a_tokens_k_and_v_are_a_twentieth_of_gpt2_larges():
    # 4 layers x 2 x 8 heads x 64 x 2 B: 8,192 B a token
    assert cs.kv_read_bytes(1, 8, 64, 4) == 8192
    # a pair costs a query head a score over 64 and a sum over 64
    assert cs.attn_flops(1, 32, 64, 1) == 32 * 2 * 128
    assert cs.prefill_read_bytes(1024, 64, 8, 64, 4) == 1024 * 8192 // 64


def test_the_steps_bytes_are_the_issues_arithmetic():
    """64 rows over ~110 k live tokens: 9.9 GB of state in and out, 6.38
    GB of weights, 0.9 GB of K and V: the state ~57% of the step."""
    total, parts = cs.decode_step_bytes(
        64, 110_000, 6.38e9, 8, 64, 4, 64, 64, 128, 4, 36)
    assert 9.6e9 < parts["state"] < 10.0e9
    assert 0.85e9 < parts["full"] < 0.95e9
    assert 0.55 < parts["state"] / total < 0.59
    # 20.9 ms at 819 GB/s: ~3,060 tokens/s
    assert 3000 < 64 / (total / 819e9) < 3120


def test_cache_bytes_a_token_over_two_kinds():
    # 54 blocks of 32 x 8,192 B and one state of 80.2 MB over 1,700 tokens
    got = cs.cache_bytes_per_token([54, 1], [32 * 8192, 80216064], 1700)
    assert round(got) == round((54 * 262144 + 80216064) / 1700)
    assert cs.cache_bytes_per_token([1, 1], [1, 1], 0) is None
