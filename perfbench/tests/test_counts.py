"""counts.py against hand-worked numbers."""
import pytest

from perfbench import counts


def test_resnet50_forward_is_4_09_gmac():
    # stem 7x7x3x64 at 112^2 = 118.0 MMAC; the published total for
    # ResNet-50 at 224 px is 4.09 GMAC (3.86 without projection shortcuts
    # and the classifier is 2.05 MMAC)
    f = counts.resnet_forward_flops_per_image(50, 1000, 224)
    assert f == 8178368512
    assert counts.resnet_train_flops_per_image(50, 1000, 224) == 3 * f


def test_resnet18_small_image_by_hand():
    # 18 layers at 64 px, 10 classes: stem 64*3*49 at 32^2; stage 0 at
    # 16^2: 4 convs 64*64*9 and a 1x1 projection 64*64; the rest halves
    macs = 64 * 3 * 49 * 32 * 32
    hw, cin = 16, 64
    for stage, w in enumerate((64, 128, 256, 512)):
        if stage:
            hw //= 2
        macs += w * cin * 9 * hw * hw + w * w * 9 * hw * hw   # unit 0
        macs += w * cin * hw * hw                             # projection
        macs += 2 * w * w * 9 * hw * hw                       # unit 1
        cin = w
    macs += 10 * 512
    assert counts.resnet_forward_flops_per_image(18, 10, 64) == 2 * macs


def test_gpt2_large_counts():
    # per layer 4 d^2 + 2 d d_ff = 19,660,800 at d 1280; 36 layers plus
    # the 50257 x 1280 logits product
    p = counts.lm_matmul_params(1280, 36, 5120, 50257)
    assert p == 36 * 19660800 + 50257 * 1280 == 772117760
    assert counts.lm_flops_per_token(1280, 36, 5120, 50257, 500) \
        == 2 * p + 36 * 4 * 500 * 1280
    # K and V of 500 positions, 36 layers, f32: 500*2*1280*4*36
    assert counts.lm_kv_bytes_per_decoded_token(1280, 36, 500) == 184320000


def test_unknown_device_is_an_error():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
