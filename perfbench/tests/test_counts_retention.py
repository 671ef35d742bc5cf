"""counts_retention.py against hand-worked numbers at ``brumby-14b``'s
widths."""
from perfbench import counts_retention as cr


def test_the_state_is_what_the_issue_reckons():
    assert cr.phi_entries(128) == 8256 and cr.phi_entries(8) == 36
    # 8 x 8,256 x 128 + 8 x 8,256 = 8.52 M float32 = 34.1 MB a layer a slot
    assert cr.state_bytes_per_slot(8, 128, 128, 1) == 8 * 8256 * 129 * 4 \
        == 34080768
    # 204.5 MB a slot over 6 layers; 24 slots = 4.91 GB
    assert cr.state_bytes_per_slot(8, 128, 128, 6) == 204484608
    assert 24 * cr.state_bytes_per_slot(8, 128, 128, 6) == 4907630592


def test_a_decode_step_moves_every_rows_state_twice():
    # 24 rows: 9.8 GB a step, 12.0 ms at 819 GB/s
    moved = cr.decode_state_bytes(24, 8, 128, 128, 6)
    assert moved == 2 * 24 * 204484608 == 9815261184
    assert 11.9e-3 < moved / 819e9 < 12.0e-3
    # bound by bytes: 1.6 operations a byte against the chip's 240
    assert cr.decode_ridge(40, 8, 128) == 104 / 64
    flops = cr.decode_flops(24, 40, 8, 128, 128, 6)
    assert flops == 24 * 6 * 104 * 8256 * 129
    assert flops / 197e12 < 0.1 * moved / 819e9


def test_the_scan_is_bound_by_the_carried_states_products():
    # a 512-token chunk: 48 heads x 2 x 8,256 x 129 a position a layer for
    # the carried state, against 40 heads x 515 a causal pair inside it
    pairs = 512 * 513 // 2
    per_layer = cr.scan_flops(512, pairs, 40, 8, 128, 128, 1)
    carried = 512 * 48 * 2 * 8256 * 129
    inside = pairs * 40 * (256 + 1 + 258)
    assert per_layer == carried + inside
    assert 0.04 < inside / carried < 0.06
    # 6 layers: 330 G operations a chunk, 1.7 ms at the bfloat16 peak
    assert 1.6e-3 < 6 * per_layer / 197e12 < 1.8e-3
    # the chunk's own state traffic is small beside it: 0.5 ms
    assert cr.scan_state_bytes(1, 8, 128, 128, 6) == 2 * 204484608
    assert cr.scan_state_bytes(1, 8, 128, 128, 6) / 819e9 < 0.5e-3
