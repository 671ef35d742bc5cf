"""Operations and bytes the algorithms need, from shapes alone.  Kept with
the benchmark so that no PR that claims a gain can change the yardstick.

A multiply-add counts as 2 operations.  A training step counts forward +
2 x forward for the backward pass (one product for the input gradient, one
for the weight gradient); recomputation does not count.
"""
from __future__ import annotations

import json
import os

from perfbench.reference import resnet as _resnet

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perfbench/peaks.json")
    return table[device_kind]


def resnet_forward_flops_per_image(num_layers=50, classes=1000, image=224):
    """2 x multiply-adds of every convolution and the classifier (BN, ReLU,
    pooling and the residual adds are not counted: they are bytes, not
    MXU work)."""
    total = 0
    for _, shape, kind, out_hw in _resnet.layer_shapes(num_layers, classes,
                                                        image):
        if kind == "conv":
            o, c, kh, kw = shape
            total += 2 * o * c * kh * kw * out_hw * out_hw
        elif kind == "fc":
            total += 2 * shape[0] * shape[1]
    return total


def resnet_train_flops_per_image(num_layers=50, classes=1000, image=224):
    return 3 * resnet_forward_flops_per_image(num_layers, classes, image)


def lm_matmul_params(d_model, n_layers, d_ff, vocab):
    """Weights that take part in a matrix product for each token (the
    tied output embedding counts once, as the logits product; the input
    embedding and positions are look-ups)."""
    per_layer = 3 * d_model * d_model + d_model * d_model \
        + 2 * d_model * d_ff
    return n_layers * per_layer + vocab * d_model


def lm_flops_per_token(d_model, n_layers, d_ff, vocab, ctx_len):
    """Forward operations for one token attending to ``ctx_len`` cached
    positions: 2 per weight, plus QK^T and PV (2 x 2 x ctx x d_model per
    layer)."""
    return 2 * lm_matmul_params(d_model, n_layers, d_ff, vocab) \
        + n_layers * 4 * ctx_len * d_model


def lm_kv_bytes_per_decoded_token(d_model, n_layers, ctx_len, bytes_per=4):
    """Bytes of K and V one decoded token has to read: its whole cached
    context, in every layer."""
    return ctx_len * 2 * d_model * bytes_per * n_layers
