"""counts_moe.py against hand-worked numbers."""
from perfbench import counts_moe


def test_expert_bytes_of_one_full_layer_pass():
    # all 128 experts of one layer: 3 x 2048 x 768 weights each, bfloat16
    assert counts_moe.expert_bytes(128, 2048, 768) == 128 * 9437184
    assert counts_moe.expert_bytes(128, 2048, 768) == 1207959552  # 1.21 GB
    # six layers, one of them reaching only 100 experts
    assert counts_moe.expert_bytes(5 * 128 + 100, 2048, 768) \
        == 740 * 3 * 2048 * 768 * 2


def test_expert_flops_of_a_block_step():
    # 64 rows x 4 positions x top-8 = 2048 assignments a layer, 6 x d x F
    # operations each
    assert counts_moe.expert_flops(64 * 4 * 8, 2048, 768) \
        == 2048 * 6 * 2048 * 768 == 19327352832


def test_block_kv_bytes():
    # 64 rows of 1000 cached positions + their block of 4, 6 layers, 4 KV
    # heads of 128 in bfloat16: 2048 B a position a layer
    assert counts_moe.block_kv_bytes(64 * 1004, 4, 128, 6) \
        == 64 * 1004 * 2048 * 6
