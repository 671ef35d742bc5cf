"""counts_mla.py against hand-worked numbers, and the reducer that cuts a
trace into programs."""
from perfbench import counts_mla, harness, trace_reduce
from perfbench.reducers import program_share


def test_latent_read_bytes_of_a_full_decode_step():
    # 256 rows of 2,000 cached positions, 5 layers, a cached vector stored
    # 640 lanes wide in bfloat16: 1,280 B a position a layer
    assert counts_mla.latent_read_bytes(256 * 2000, 640, 5) \
        == 512000 * 1280 * 5 == 3276800000           # 3.3 GB
    # as wide as it is (576): the issue's 1,152 B
    assert counts_mla.latent_read_bytes(1, 576, 1) == 1152


def test_latent_flops_of_a_full_decode_step():
    # 128 heads x (576 score + 512 value) x 2 a position a layer
    assert counts_mla.latent_flops(1, 128, 576, 512, 1) == 278528
    assert counts_mla.latent_flops(256 * 2000, 128, 576, 512, 5) \
        == 512000 * 278528 * 5 == 713031680000       # 0.71 T


def test_the_decode_body_sits_at_the_ridge():
    # 242 operations a byte as wide as the vector is, 218 as stored;
    # the v5e's ridge is 197e12 / 819e9 = 240.5
    assert round(counts_mla.decode_ridge(128, 576, 512, 576), 1) == 241.8
    assert round(counts_mla.decode_ridge(128, 576, 512, 640), 1) == 217.6


def test_prefill_reads_a_tile_s_share():
    # a tile of 2 tokens reads its context once for both
    assert counts_mla.latent_prefill_read_bytes(1000, 2, 640, 5) \
        == counts_mla.latent_read_bytes(1000, 640, 5) // 2 == 3200000


def test_held_expert_bytes_and_flops():
    # all 16 held experts of the 4 expert layers: 3 x 7168 x 2048 bf16 each
    assert counts_mla.held_expert_bytes(64, 7168, 2048) \
        == 64 * 88080384 == 5637144576               # 5.6 GB of the 8.9
    # 256 rows x top-8 over 256 experts, 16 held: 128 a layer, 4 layers
    assert counts_mla.held_expert_flops(512, 7168, 2048) \
        == 512 * 6 * 7168 * 2048 == 45097156608


def _trace(evs):
    return trace_reduce.Trace((0, 1000), {"/device:TPU:0": sorted(evs)}, [])


def test_program_share_cuts_at_the_pause_between_programs():
    dec, pre = "%_mla_call_w512_decode = x", "%_mla_call_w512_t512_prefill = x"
    evs = [(0, 10, "%fusion.1"), (10, 30, dec), (30, 40, "%fusion.2"),
           # pause 40-60, then a prefill program
           (60, 70, "%fusion.1"), (70, 150, pre), (150, 170, "%fusion.9"),
           (171, 180, pre), (180, 200, "%fusion.3"),
           # pause, then decode again
           (230, 240, "%fusion.1"), (240, 260, dec), (260, 300, "%fusion.4")]
    src = harness.Sources(trace=_trace(evs))
    got = program_share.reduce(
        {"num": r"_mla_call_w\d+_t\d+_prefill", "markers": r"_mla_call_w\d+_"},
        src)
    prefill = 10 + 80 + 20 + 9 + 20
    total = sum(e - s for s, e, _ in evs)
    assert abs(got - 100.0 * prefill / total) < 1e-9


def test_program_share_finds_nothing_without_its_kernels():
    src = harness.Sources(trace=_trace([(0, 10, "%fusion.1")]))
    assert program_share.reduce({"num": "a", "markers": "_mla_call"},
                                src) is None
    assert program_share.reduce({"num": "a", "markers": "b"},
                                harness.Sources()) is None
