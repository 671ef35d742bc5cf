"""la_op completion + deformable conv / PSROI / sync BN (VERDICT r3 item 7).

Oracle style follows the reference's test strategy (SURVEY.md §4): numpy /
scipy oracles and cross-backend consistency.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd


def _spd(n, batch=(), seed=0):
    r = np.random.RandomState(seed)
    a = r.rand(*batch, n, n).astype(np.float32)
    return a @ a.swapaxes(-1, -2) + n * np.eye(n, dtype=np.float32)


def test_linalg_gemm():
    r = np.random.RandomState(0)
    a, b, c = r.rand(3, 4), r.rand(4, 5), r.rand(3, 5)
    out = nd.linalg.gemm(nd.array(a), nd.array(b), nd.array(c),
                         alpha=2.0, beta=0.5).asnumpy()
    assert np.allclose(out, 2.0 * (a @ b) + 0.5 * c, atol=1e-5)
    out = nd.linalg.gemm(nd.array(a.T), nd.array(b), nd.array(c),
                         transpose_a=True).asnumpy()
    assert np.allclose(out, a @ b + c, atol=1e-5)


def test_linalg_potri():
    spd = _spd(4)
    L = np.linalg.cholesky(spd)
    out = nd.linalg.potri(nd.array(L)).asnumpy()
    assert np.allclose(out, np.linalg.inv(spd), atol=1e-4)


def test_linalg_trmm():
    r = np.random.RandomState(1)
    a = np.tril(r.rand(4, 4)).astype(np.float32)
    b = r.rand(4, 3).astype(np.float32)
    out = nd.linalg.trmm(nd.array(a), nd.array(b), alpha=2.0).asnumpy()
    assert np.allclose(out, 2.0 * a @ b, atol=1e-5)
    out = nd.linalg.trmm(nd.array(a), nd.array(b.T), rightside=True).asnumpy()
    assert np.allclose(out, b.T @ a, atol=1e-5)
    out = nd.linalg.trmm(nd.array(a), nd.array(b), transpose=True).asnumpy()
    assert np.allclose(out, a.T @ b, atol=1e-5)


def test_linalg_gelqf():
    r = np.random.RandomState(2)
    a = r.rand(3, 6).astype(np.float32)
    q, l = nd.linalg.gelqf(nd.array(a))
    q, l = q.asnumpy(), l.asnumpy()
    assert np.allclose(l @ q, a, atol=1e-4)           # A = L Q
    assert np.allclose(q @ q.T, np.eye(3), atol=1e-4)  # row-orthonormal
    assert np.allclose(np.triu(l, 1), 0, atol=1e-5)    # L lower triangular
    assert (np.diag(l) > 0).all()


def test_linalg_syevd():
    a = _spd(5, seed=3)
    u, w = nd.linalg.syevd(nd.array(a))
    u, w = u.asnumpy(), w.asnumpy()
    # U A = diag(L) U, ascending eigenvalues
    assert np.allclose(u @ a, np.diag(w) @ u, atol=1e-3)
    assert np.allclose(u @ u.T, np.eye(5), atol=1e-4)
    assert (np.diff(w) >= -1e-5).all()


def test_linalg_sumlogdiag():
    a = _spd(4, batch=(2,), seed=4)
    out = nd.linalg.sumlogdiag(nd.array(a)).asnumpy()
    ref = np.log(np.diagonal(a, axis1=-2, axis2=-1)).sum(-1)
    assert np.allclose(out, ref, atol=1e-5)


def test_linalg_makediag_extractdiag():
    v = np.arange(1.0, 4.0, dtype=np.float32)
    m = nd.linalg.makediag(nd.array(v), offset=1).asnumpy()
    assert m.shape == (4, 4)
    assert np.allclose(np.diag(m, 1), v)
    back = nd.linalg.extractdiag(nd.array(m), offset=1).asnumpy()
    assert np.allclose(back, v)


def test_linalg_grad_flows():
    """Autograd through the new la_ops (vjp provided by jax)."""
    from mxnet_tpu import autograd
    a = nd.array(_spd(3, seed=5))
    a.attach_grad()
    with autograd.record():
        y = nd.linalg.sumlogdiag(a)
    y.backward()
    g = a.grad.asnumpy()
    expect = np.diag(1.0 / np.diag(a.asnumpy()))
    assert np.allclose(g, expect, atol=1e-5)


# ---------------------------------------------------------------------------
# deformable convolution
# ---------------------------------------------------------------------------

def test_deformable_conv_zero_offset_matches_conv():
    r = np.random.RandomState(0)
    x = r.rand(2, 4, 9, 9).astype(np.float32)
    w = (r.rand(6, 4, 3, 3).astype(np.float32) - 0.5)
    b = r.rand(6).astype(np.float32)
    off = np.zeros((2, 2 * 9, 7, 7), np.float32)
    out = nd.contrib.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), nd.array(b),
        kernel=(3, 3), num_filter=6).asnumpy()
    ref = nd.Convolution(nd.array(x), nd.array(w), nd.array(b),
                         kernel=(3, 3), num_filter=6).asnumpy()
    assert out.shape == ref.shape == (2, 6, 7, 7)
    assert np.allclose(out, ref, atol=1e-4)


def test_deformable_conv_integer_shift():
    """A constant integer offset equals convolving a shifted image inside
    the valid interior."""
    r = np.random.RandomState(1)
    x = r.rand(1, 2, 10, 10).astype(np.float32)
    w = r.rand(3, 2, 3, 3).astype(np.float32)
    off = np.zeros((1, 18, 8, 8), np.float32)
    off[:, 0::2] = 1.0  # shift all taps one row down
    out = nd.contrib.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), None,
        kernel=(3, 3), num_filter=3, no_bias=True).asnumpy()
    ref = nd.Convolution(nd.array(x[:, :, 1:, :]), nd.array(w), None,
                         kernel=(3, 3), num_filter=3, no_bias=True).asnumpy()
    assert np.allclose(out[:, :, :7], ref[:, :, :7], atol=1e-4)


def test_deformable_conv_stride_pad_groups():
    r = np.random.RandomState(2)
    x = r.rand(1, 4, 8, 8).astype(np.float32)
    w = r.rand(4, 2, 3, 3).astype(np.float32)
    off = np.zeros((1, 18, 4, 4), np.float32)
    out = nd.contrib.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), None, kernel=(3, 3),
        stride=(2, 2), pad=(1, 1), num_filter=4, num_group=2,
        no_bias=True).asnumpy()
    ref = nd.Convolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                         stride=(2, 2), pad=(1, 1), num_filter=4,
                         num_group=2, no_bias=True).asnumpy()
    assert np.allclose(out, ref, atol=1e-4)


# ---------------------------------------------------------------------------
# PSROI pooling
# ---------------------------------------------------------------------------

def test_psroi_pooling_uniform():
    """On channel-constant score maps each output bin returns its own
    group's constant."""
    OD, G = 2, 3
    C = OD * G * G
    data = np.zeros((1, C, 12, 12), np.float32)
    for c in range(C):
        data[0, c] = c
    rois = np.array([[0, 0, 0, 11, 11]], np.float32)
    out = nd.contrib.PSROIPooling(nd.array(data), nd.array(rois),
                                  spatial_scale=1.0, output_dim=OD,
                                  pooled_size=G, group_size=G).asnumpy()
    assert out.shape == (1, OD, G, G)
    for ct in range(OD):
        for py in range(G):
            for px in range(G):
                expect = (ct * G + py) * G + px
                assert abs(out[0, ct, py, px] - expect) < 1e-4, \
                    (ct, py, px, out[0, ct, py, px])


def test_psroi_pooling_subregion():
    data = np.zeros((1, 4, 10, 10), np.float32)
    data[0, :, :5] = 1.0   # top half ones
    rois = np.array([[0, 0, 0, 9, 4]], np.float32)  # top half roi
    out = nd.contrib.PSROIPooling(nd.array(data), nd.array(rois),
                                  spatial_scale=1.0, output_dim=4,
                                  pooled_size=1, group_size=1).asnumpy()
    assert np.allclose(out, 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# sync BatchNorm
# ---------------------------------------------------------------------------

def test_sync_batch_norm_matches_batch_norm_single():
    r = np.random.RandomState(0)
    x = r.rand(4, 3, 5, 5).astype(np.float32)
    g = np.ones(3, np.float32)
    b = np.zeros(3, np.float32)
    rm = np.zeros(3, np.float32)
    rv = np.ones(3, np.float32)
    from mxnet_tpu import autograd
    with autograd.record(train_mode=True):
        a = nd.contrib.SyncBatchNorm(nd.array(x), nd.array(g), nd.array(b),
                                     nd.array(rm), nd.array(rv),
                                     fix_gamma=False).asnumpy()
        c = nd.BatchNorm(nd.array(x), nd.array(g), nd.array(b),
                         nd.array(rm), nd.array(rv),
                         fix_gamma=False).asnumpy()
    assert np.allclose(a, c, atol=1e-5)


def test_sync_batch_norm_shard_map_global_stats():
    """Under shard_map with axis_name, per-device SyncBatchNorm equals
    full-batch BatchNorm (the cross-device guarantee the reference's op
    provides over NCCL — here over mesh collectives)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.ops.contrib import sync_batch_norm
    from mxnet_tpu.ops.nn import batch_norm

    devs = np.array(jax.devices()[:8]).reshape(8)
    mesh = Mesh(devs, ("dp",))
    r = np.random.RandomState(1)
    x = r.rand(16, 4, 3, 3).astype(np.float32) * 3 + 1
    g = np.ones(4, np.float32)
    b = np.zeros(4, np.float32)
    rm = np.zeros(4, np.float32)
    rv = np.ones(4, np.float32)

    def local(xl):
        return sync_batch_norm(xl, g, b, rm, rv, fix_gamma=False,
                               axis_name="dp", _training=True)

    out = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("dp"),
                                out_specs=P("dp"), check_vma=False))(
        jnp.asarray(x))
    ref = batch_norm(jnp.asarray(x), g, b, rm, rv, fix_gamma=False,
                     _training=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
