#!/usr/bin/env python
"""On-TPU correctness tier: a curated op + gluon-layer subset executed on
the real chip AND on the host CPU backend from identical inputs, compared
case by case — the reference's same-op-two-backends oracle
(tests/python/gpu/test_operator_gpu.py) with TPU standing in for GPU.

Writes chiprun_out/tpu_parity.json (override with --out) INCREMENTALLY
after every case, so a run that is cut still leaves a partial artifact.
One process holds the chip and runs both legs (the CPU backend lives
beside the TPU one):

    python tools/tpu_parity.py [--only SUBSTRING] [--out FILE]

Exit 0 iff every executed case passed.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_cases():
    """Returns [(name, fn)] where fn() computes outputs under the ambient
    default context and returns a list of numpy arrays."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    rng = np.random.RandomState(0)
    a4 = rng.randn(4, 16).astype(np.float32)
    b4 = rng.randn(4, 16).astype(np.float32)
    m1 = rng.randn(8, 12).astype(np.float32)
    m2 = rng.randn(12, 6).astype(np.float32)
    img = rng.randn(2, 8, 14, 14).astype(np.float32)
    img_hwc = rng.randn(2, 14, 14, 8).astype(np.float32)
    seq = rng.randn(5, 3, 10).astype(np.float32)
    spd = np.abs(rng.randn(3, 3)).astype(np.float32) + 3 * np.eye(3, dtype=np.float32)
    idx = rng.randint(0, 16, (4,)).astype(np.float32)

    def case(f, *arrs):
        def run():
            nds = [nd.array(a) for a in arrs]
            out = f(*nds)
            outs = out if isinstance(out, (list, tuple)) else [out]
            return [np.asarray(o.asnumpy()) for o in outs]

        return run

    cases = [
        # elementwise / math
        ("exp", case(lambda x: nd.exp(x), a4)),
        ("log", case(lambda x: nd.log(nd.abs(x) + 1.0), a4)),
        ("tanh", case(lambda x: nd.tanh(x), a4)),
        ("erf", case(lambda x: nd.erf(x), a4)),
        ("sqrt", case(lambda x: nd.sqrt(nd.abs(x)), a4)),
        ("rsqrt", case(lambda x: nd.rsqrt(nd.abs(x) + 1.0), a4)),
        ("sigmoid", case(lambda x: nd.sigmoid(x), a4)),
        ("relu", case(lambda x: nd.relu(x), a4)),
        ("broadcast_add", case(lambda x, y: nd.broadcast_add(x, y), a4, b4)),
        ("broadcast_maximum", case(lambda x, y: nd.broadcast_maximum(x, y),
                                   a4, b4)),
        ("clip", case(lambda x: nd.clip(x, -0.5, 0.5), a4)),
        ("where", case(lambda x, y: nd.where(x > 0, x, y), a4, b4)),
        # reductions / ordering
        ("sum_axis", case(lambda x: nd.sum(x, axis=1), a4)),
        ("max_axis", case(lambda x: nd.max(x, axis=0), a4)),
        ("argmax", case(lambda x: nd.argmax(x, axis=1), a4)),
        ("topk", case(lambda x: nd.topk(x, k=3, ret_typ="value"), a4)),
        ("sort", case(lambda x: nd.sort(x, axis=1), a4)),
        ("reverse", case(lambda x: nd.reverse(x, axis=1), a4)),
        # matmul family (MXU)
        ("dot", case(lambda x, y: nd.dot(x, y), m1, m2)),
        ("batch_dot", case(lambda x, y: nd.batch_dot(x, y),
                           rng.randn(3, 4, 5).astype(np.float32),
                           rng.randn(3, 5, 2).astype(np.float32))),
        ("FullyConnected", case(
            lambda x, w, b: nd.FullyConnected(x, w, b, num_hidden=6),
            m1, rng.randn(6, 12).astype(np.float32),
            np.zeros(6, np.float32))),
        ("linalg_gemm2", case(lambda x, y: nd.linalg_gemm2(x, y), m1, m2)),
        ("linalg_potrf", case(lambda x: nd.linalg_potrf(x), spd)),
        # conv / pool / norm
        ("Convolution", case(
            lambda x, w, b: nd.Convolution(x, w, b, kernel=(3, 3),
                                           num_filter=4, pad=(1, 1)),
            img, rng.randn(4, 8, 3, 3).astype(np.float32) * 0.1,
            np.zeros(4, np.float32))),
        ("Pooling_max", case(
            lambda x: nd.Pooling(x, kernel=(2, 2), pool_type="max",
                                 stride=(2, 2)), img)),
        ("Pooling_avg", case(
            lambda x: nd.Pooling(x, kernel=(2, 2), pool_type="avg",
                                 stride=(2, 2)), img)),
        ("BatchNorm_train", case(
            lambda x, g, b, mm, mv: nd.BatchNorm(
                x, g, b, mm, mv, fix_gamma=False, output_mean_var=False),
            img, np.abs(rng.randn(8)).astype(np.float32),
            rng.randn(8).astype(np.float32), np.zeros(8, np.float32),
            np.ones(8, np.float32))),
        ("LayerNorm", case(
            lambda x, g, b: nd.LayerNorm(x, g, b),
            a4, np.ones(16, np.float32), np.zeros(16, np.float32))),
        ("softmax", case(lambda x: nd.softmax(x, axis=-1), a4)),
        ("log_softmax", case(lambda x: nd.log_softmax(x, axis=-1), a4)),
        # indexing
        ("take", case(lambda x, i: nd.take(x, i, axis=0), m1,
                      rng.randint(0, 8, (3,)).astype(np.float32))),
        ("Embedding", case(
            lambda i, w: nd.Embedding(i, w, input_dim=16, output_dim=5),
            idx, rng.randn(16, 5).astype(np.float32))),
        ("one_hot", case(lambda i: nd.one_hot(i, depth=16), idx)),
        ("gather_nd", case(
            lambda x, i: nd.gather_nd(x, i), m1,
            np.array([[0, 2], [1, 3]], np.float32))),
        ("transpose", case(lambda x: nd.transpose(x, axes=(1, 0)), m1)),
        ("reshape", case(lambda x: nd.reshape(x, (2, -1)), m1)),
        ("slice", case(lambda x: nd.slice(x, begin=(1, 2), end=(5, 9)), m1)),
        ("tile", case(lambda x: nd.tile(x, reps=(2, 1)), a4)),
        ("concat", case(lambda x, y: nd.concat(x, y, dim=1), a4, b4)),
        # losses / output heads
        ("SoftmaxOutput", case(
            lambda x, l: nd.SoftmaxOutput(x, l), a4,
            rng.randint(0, 16, (4,)).astype(np.float32))),
        ("smooth_l1", case(lambda x: nd.smooth_l1(x, scalar=1.0), a4)),
        # sequence / rnn
        ("SequenceMask", case(
            lambda x, l: nd.SequenceMask(x, l, use_sequence_length=True,
                                         value=-1.0),
            seq, np.array([3, 5, 2], np.float32))),
        ("SequenceReverse", case(
            lambda x: nd.SequenceReverse(x), seq)),
        # image ops
        ("image_normalize", case(
            lambda x: nd._image_normalize(x, mean=(0.5,), std=(0.25,)),
            rng.rand(3, 8, 8).astype(np.float32))),
        ("image_resize_bilinear", case(
            lambda x: nd.contrib_BilinearResize2D(x, height=7, width=9)
            if hasattr(nd, "contrib_BilinearResize2D")
            else nd.contrib.BilinearResize2D(x, height=7, width=9), img)),
        ("adjust_lighting", case(
            lambda x: nd._image_adjust_lighting(x, alpha=(0.02, -0.01, 0.03)),
            rng.rand(3, 6, 6).astype(np.float32) * 255)),
        # optimizer / quantization kernels
        ("sgd_mom_update", case(
            lambda w, g, m: nd.sgd_mom_update(w, g, m, lr=0.1, momentum=0.9),
            a4.copy(), b4.copy(), np.zeros_like(a4))),
        ("adam_update", case(
            lambda w, g, m, v: nd.adam_update(w, g, m, v, lr=0.01),
            a4.copy(), b4.copy(), np.zeros_like(a4), np.zeros_like(a4))),
    ]

    # gluon layers: params captured on the FIRST run and force-loaded on
    # the second, so both backends compute from identical weights
    def gluon_case(make, x):
        state = {}

        def run():
            net = make()
            net.initialize()
            net(nd.array(x))  # materialize deferred shapes
            # keyed by ORDER: gluon prefixes carry a per-instance counter
            # (dense1_ vs dense2_), so names differ between the two runs
            plist = list(net.collect_params().values())
            if "params" in state:
                for p, arr in zip(plist, state["params"]):
                    p.set_data(nd.array(arr))
            else:
                state["params"] = [p.data().asnumpy() for p in plist]
            out = net(nd.array(x))
            return [np.asarray(out.asnumpy())]

        return run

    cases += [
        ("gluon_Dense", gluon_case(lambda: gluon.nn.Dense(5), m1)),
        ("gluon_Conv2D", gluon_case(
            lambda: gluon.nn.Conv2D(4, 3, padding=1), img)),
        ("gluon_Conv2D_NHWC", gluon_case(
            lambda: gluon.nn.Conv2D(4, 3, padding=1, layout="NHWC"),
            img_hwc)),
        ("gluon_LSTM", gluon_case(
            lambda: gluon.rnn.LSTM(7, layout="TNC"), seq)),
        ("gluon_resnet18_stem", gluon_case(
            lambda: gluon.model_zoo.vision.resnet18_v1(classes=10).features,
            rng.rand(1, 3, 32, 32).astype(np.float32))),
    ]

    # pallas kernels: interpret (CPU) vs native TPU (Mosaic) lowering.
    # Inputs are hoisted — a closure drawing from `rng` would advance the
    # stream between the two backend runs and compare different data.  The
    # CPU leg must FORCE the interpreter and place inputs on the CPU device:
    # without that, both legs on a TPU host would run the same native
    # kernel and the comparison would be vacuous.
    q_flash = rng.rand(1, 32, 2, 16).astype(np.float32)
    x_bn = rng.randn(2, 4, 4, 128).astype(np.float32)

    def _pallas_leg(fn):
        import os

        import jax

        import mxnet_tpu as mx

        ctx = mx.context.current_context()
        on_cpu = ctx.jax_device.platform == "cpu"
        # TPUMX_PALLAS=1 keeps the gated call sites (flash backward, fused
        # LN, paged decode) on their kernels for BOTH legs — the comparison
        # is interpreter-vs-Mosaic of the same kernel, never kernel-vs-XLA
        prev = {k: os.environ.get(k)
                for k in ("TPUMX_PALLAS_INTERPRET", "TPUMX_PALLAS")}
        os.environ["TPUMX_PALLAS_INTERPRET"] = "1" if on_cpu else "0"
        os.environ["TPUMX_PALLAS"] = "1"
        try:
            put = lambda a: jax.device_put(a, ctx.jax_device)  # noqa: E731
            return fn(put)
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def pallas_flash():
        from mxnet_tpu.ops import pallas_kernels as pk

        def body(put):
            q = put(q_flash)
            return [np.asarray(pk.flash_attention(q, q, q, causal=True))]

        return _pallas_leg(body)

    def pallas_bn():
        from mxnet_tpu.ops import pallas_kernels as pk

        def body(put):
            out, mean, var = pk.bn_train_fused(
                put(x_bn), put(np.ones(128, np.float32)),
                put(np.zeros(128, np.float32)), 1e-3, -1)
            return [np.asarray(out), np.asarray(mean), np.asarray(var)]

        return _pallas_leg(body)

    # the PR-9 kernel layer (docs/pallas.md): flash backward, fused LN and
    # paged decode attention join the two-backend sweep.  Inputs hoisted
    # like q_flash/x_bn above.
    g_flash = rng.rand(1, 32, 2, 16).astype(np.float32)
    x_ln = rng.randn(4, 8, 256).astype(np.float32)
    g_ln = (rng.rand(256) + 0.5).astype(np.float32)
    b_ln = rng.randn(256).astype(np.float32)
    q_paged = rng.randn(3, 1, 2, 16).astype(np.float32)
    kp_paged = rng.randn(8, 4, 2 * 16).astype(np.float32)
    vp_paged = rng.randn(8, 4, 2 * 16).astype(np.float32)
    tbl_paged = np.array([[1, 2, 0], [3, 0, 0], [0, 0, 0]], np.int32)
    pos_paged = np.array([[6], [2], [0]], np.int32)
    maxpos_paged = np.array([6, 2, -1], np.int32)
    # the kernel is handed the WHOLE layered pool and a layer index: these
    # pages (4 x 32: part tiles) take the chunk body even at T == 1
    layered = lambda a: np.stack([np.zeros_like(a), a])
    # ...and whole-tile pages (16 x 128) take the decode body, which
    # fetches its own pages: 3 a trip here, rows of 1 / 2 / 0 trips, a
    # null entry inside the table.  Its own stream: the draws above and
    # below keep their values.
    rng_dec = np.random.RandomState(25)
    q_dec = rng_dec.randn(4, 1, 2, 64).astype(np.float32)
    kp_dec = rng_dec.randn(2, 12, 16, 128).astype(np.float32)
    vp_dec = rng_dec.randn(2, 12, 16, 128).astype(np.float32)
    tbl_dec = np.array([[1, 2, 0], [3, 4, 5], [0, 0, 0], [6, 0, 7]],
                       np.int32)
    pos_dec = np.array([[20], [47], [0], [40]], np.int32)
    maxpos_dec = np.array([20, 47, -1, 40], np.int32)

    def pallas_flash_bwd():
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.ops import pallas_kernels as pk

        def body(put):
            q = put(q_flash)
            g = put(g_flash)
            grads = jax.grad(
                lambda q_, k_, v_: jnp.sum(
                    pk.flash_attention(q_, k_, v_, causal=True) * g),
                argnums=(0, 1, 2))(q, q, q)
            return [np.asarray(a) for a in grads]

        return _pallas_leg(body)

    def pallas_layer_norm():
        from mxnet_tpu.ops import pallas_kernels as pk

        def body(put):
            out = pk.layer_norm_fused(put(x_ln), put(g_ln), put(b_ln))
            out_g = pk.layer_norm_fused(put(x_ln), put(g_ln), put(b_ln),
                                        gelu=True)
            return [np.asarray(out), np.asarray(out_g)]

        return _pallas_leg(body)

    def pallas_paged():
        from mxnet_tpu.ops import paged_attention as pa

        def body(put):
            out = pa.paged_attention(
                put(q_paged), put(layered(kp_paged)),
                put(layered(vp_paged)), put(tbl_paged), put(pos_paged),
                put(maxpos_paged), layer=1)
            return [np.asarray(out)]

        return _pallas_leg(body)

    def pallas_paged_decode():
        from mxnet_tpu.ops import paged_attention as pa

        def body(put):
            assert pa._decode_pages(1, 3, 16, 128, kp_dec.dtype, False) == 3
            out = pa.paged_attention(
                put(q_dec), put(kp_dec), put(vp_dec), put(tbl_dec),
                put(pos_dec), put(maxpos_dec), layer=1)
            return [np.asarray(out)]

        return _pallas_leg(body)

    cases += [("pallas_flash_attention", pallas_flash),
              ("pallas_bn_train_fused", pallas_bn),
              ("pallas_flash_attention_bwd", pallas_flash_bwd),
              ("pallas_layer_norm_fused", pallas_layer_norm),
              ("pallas_paged_attention", pallas_paged),
              ("pallas_paged_attention_decode", pallas_paged_decode)]

    # int8 quantization family (docs/quantization.md): the serving
    # quantize/dequantize kernels and the quantized FC/conv twins run as
    # plain registered ops on both backends...
    x_q = rng.randn(4, 16).astype(np.float32)
    w_q = (rng.randn(6, 16) * 0.2).astype(np.float32)
    ws_q = (np.abs(w_q).max(axis=1) / 127.0).astype(np.float32)
    wq_q = np.clip(np.round(w_q / ws_q[:, None]), -127, 127).astype(np.int8)
    img_q = rng.randn(2, 4, 8, 8).astype(np.float32)
    ck_q = (rng.randn(3, 4, 3, 3) * 0.1).astype(np.float32)
    cks_q = (np.abs(ck_q).reshape(3, -1).max(axis=1) / 127.0).astype(
        np.float32)
    ckq_q = np.clip(np.round(ck_q / cks_q[:, None, None, None]), -127,
                    127).astype(np.int8)

    cases += [
        ("quantize_dequantize_int8", case(
            lambda x: nd._tpumx_dequantize_int8(
                *nd._tpumx_quantize_int8(x, scale=0.05)), x_q)),
        ("quantized_fc_int8", case(
            lambda x, w, s, b: nd._tpumx_quantized_fc_int8(
                *nd._tpumx_quantize_int8(x), w, s, b, num_hidden=6),
            x_q, wq_q, ws_q, np.zeros(6, np.float32))),
        ("quantized_conv_int8", case(
            lambda x, w, s: nd._tpumx_quantized_conv_int8(
                *nd._tpumx_quantize_int8(x), w, s, kernel=(3, 3),
                num_filter=3, pad=(1, 1), no_bias=True),
            img_q, ckq_q, cks_q)),
    ]

    # ...and the INT8-POOL paged-attention variant joins the Pallas
    # two-backend sweep with the same leg-forcing pattern as the PR 9
    # entries: per-(block, head) scales ride the scalar-prefetch/VMEM
    # path next to the block tables.
    kq_paged = rng.randint(-127, 128, kp_paged.shape).astype(np.int8)
    vq_paged = rng.randint(-127, 128, vp_paged.shape).astype(np.int8)
    ks_paged = (np.abs(rng.randn(8, 2)) * 0.02 + 0.01).astype(np.float32)
    vs_paged = (np.abs(rng.randn(8, 2)) * 0.02 + 0.01).astype(np.float32)

    def pallas_paged_int8():
        from mxnet_tpu.ops import paged_attention as pa

        def body(put):
            out = pa.paged_attention(
                put(q_paged), put(layered(kq_paged)),
                put(layered(vq_paged)), put(tbl_paged), put(pos_paged),
                put(maxpos_paged), k_scale=put(ks_paged),
                v_scale=put(vs_paged), layer=1)
            return [np.asarray(out)]

        return _pallas_leg(body)

    cases += [("pallas_paged_attention_int8", pallas_paged_int8)]

    # the prefix-cache CoW block copy (docs/generation.md "prefix
    # caching"): the donated in-program pool move that gives a writer a
    # private tail block before its first scatter — f32 and int8 pool
    # variants (scales travel with the block) join the two-backend sweep.
    # Inputs hoisted like the Pallas entries above.
    kp_cow = rng.randn(2, 6, 4, 2 * 8).astype(np.float32)
    vp_cow = rng.randn(2, 6, 4, 2 * 8).astype(np.float32)
    kq_cow = rng.randint(-127, 128, kp_cow.shape).astype(np.int8)
    vq_cow = rng.randint(-127, 128, vp_cow.shape).astype(np.int8)
    ks_cow = (np.abs(rng.randn(2, 6, 2)) * 0.02 + 0.01).astype(np.float32)
    vs_cow = (np.abs(rng.randn(2, 6, 2)) * 0.02 + 0.01).astype(np.float32)
    src_cow = np.array([3], np.int32)
    dst_cow = np.array([5], np.int32)

    def _device_case(fn):
        def run():
            import jax

            import mxnet_tpu as mx

            ctx = mx.context.current_context()
            put = lambda a: jax.device_put(a, ctx.jax_device)  # noqa: E731
            return fn(put)

        return run

    def kv_block_copy(put):
        import jax

        from mxnet_tpu.serving.generation.programs import block_copy_pools

        k, v = jax.jit(block_copy_pools)(
            (put(kp_cow), put(vp_cow)), put(src_cow), put(dst_cow))
        return [np.asarray(k), np.asarray(v)]

    def kv_block_copy_int8(put):
        import jax

        from mxnet_tpu.serving.generation.programs import block_copy_pools

        k, v, ks, vs = jax.jit(block_copy_pools)(
            (put(kq_cow), put(vq_cow), put(ks_cow), put(vs_cow)),
            put(src_cow), put(dst_cow))
        return [np.asarray(k).astype(np.float32),
                np.asarray(v).astype(np.float32),
                np.asarray(ks), np.asarray(vs)]

    cases += [("kv_block_copy_cow", _device_case(kv_block_copy)),
              ("kv_block_copy_cow_int8", _device_case(kv_block_copy_int8))]

    # the speculative-decoding pair (docs/generation.md "Speculative
    # decoding"): the exact-match rejection sampler and the multi-query
    # verify step — a mid-sequence (B, s+1) chunk through the cache-aware
    # decode path followed by speculative_verify on its logits, exactly
    # the engine's one-dispatch verify iteration.  Inputs hoisted like
    # the entries above.
    logits_sv = rng.randn(2, 4, 19).astype(np.float32)
    fed_sv = rng.randint(0, 19, (2, 4)).astype(np.int32)
    seeds_sv = np.array([7, 9], np.uint32)
    ctr_sv = np.array([11, 4], np.uint32)
    temp_sv = np.array([0.0, 0.8], np.float32)
    topk_sv = np.array([0, 5], np.int32)
    topp_sv = np.array([1.0, 0.9], np.float32)
    len_sv = np.array([4, 3], np.int32)
    prompt_sv = rng.randint(0, 19, (1, 8)).astype(np.int32)
    verify_sv = rng.randint(0, 19, (1, 4)).astype(np.int32)

    def spec_rejection_sampler(put):
        import jax

        from mxnet_tpu.ops import sampling as smp

        tgt, acc = jax.jit(smp.speculative_verify)(
            put(logits_sv), put(fed_sv), put(seeds_sv), put(ctr_sv),
            put(temp_sv), put(topk_sv), put(topp_sv), put(len_sv))
        return [np.asarray(tgt), np.asarray(acc)]

    def spec_verify_step(put):
        import functools

        import jax

        from mxnet_tpu.ops import sampling as smp
        from mxnet_tpu.parallel import transformer as tr

        cfg = tr.TransformerConfig(vocab=19, d_model=16, n_heads=2,
                                   n_layers=2, d_ff=32, max_len=32)
        params = put(tr.transformer_lm_init(cfg, jax.random.PRNGKey(2)))
        kp = put(np.zeros((2, 4, 8, 2 * 8), np.float32))
        vp = put(np.zeros((2, 4, 8, 2 * 8), np.float32))
        tbl = put(np.array([[1, 2]], np.int32))
        step = jax.jit(functools.partial(tr.transformer_lm_decode, cfg=cfg))
        # prefill the 8-token context...
        _, kp, vp = step(params, put(prompt_sv),
                         put(np.arange(8, dtype=np.int32)[None]),
                         put(np.array([8], np.int32)), kp, vp, tbl)
        # ...then ONE (1, 4) verify chunk at positions 8..11 and the
        # rejection sampler over its per-position logits
        logits, kp, vp = step(params, put(verify_sv),
                              put(np.arange(8, 12, dtype=np.int32)[None]),
                              put(np.array([4], np.int32)), kp, vp, tbl)
        tgt, acc = jax.jit(smp.speculative_verify)(
            logits, put(verify_sv), put(seeds_sv[:1]), put(ctr_sv[:1]),
            put(temp_sv[:1]), put(topk_sv[:1]), put(topp_sv[:1]),
            put(len_sv[:1]))
        return [np.asarray(logits), np.asarray(kp), np.asarray(vp),
                np.asarray(tgt), np.asarray(acc)]

    cases += [("spec_rejection_sampler", _device_case(spec_rejection_sampler)),
              ("spec_verify_step", _device_case(spec_verify_step))]
    return cases


def main():
    self_test = "--self-test" in sys.argv
    if "--out" in sys.argv:
        i = sys.argv.index("--out")
        if i + 1 >= len(sys.argv):
            print("usage: tpu_parity.py [--self-test] [--out FILE]",
                  file=sys.stderr)
            return 2
        out_path = sys.argv[i + 1]
    elif self_test:
        # a hermetic CPU-vs-CPU self-test must never masquerade as the
        # round's on-chip parity artifact
        out_path = "/tmp/tpu_parity_selftest.json"
    else:
        out_path = os.path.join(REPO, "chiprun_out", "tpu_parity.json")
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.util import enable_compile_cache

    enable_compile_cache()

    tpu_ctx = mx.tpu() if any(d.platform != "cpu" for d in jax.devices()) \
        else (mx.cpu() if self_test else None)
    if tpu_ctx is None:
        print("no accelerator visible; refusing to write a CPU-vs-CPU "
              "artifact (--self-test exercises the cases hermetically)",
              file=sys.stderr)
        return 2
    platform = tpu_ctx.jax_device.platform
    cases = build_cases()
    if "--only" in sys.argv:  # e.g. --only pallas: the kernel-layer cases
        want = sys.argv[sys.argv.index("--only") + 1]
        cases = [(n, fn) for n, fn in cases if want in n]
    record = {"platform": platform, "started": time.strftime("%F %T"),
              "n_cases": len(cases), "results": [], "done": False}

    def flush():
        # atomic: a SIGTERM/SIGKILL landing mid-write must not destroy the
        # previously flushed results — that partial artifact is the whole
        # point of incremental flushing
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
        os.replace(tmp, out_path)

    flush()
    n_fail = 0
    for name, fn in cases:
        t0 = time.time()
        entry = {"name": name}
        try:
            with mx.cpu():
                ref = fn()
            with tpu_ctx:
                got = fn()
            errs = []
            ok = len(ref) == len(got)
            for r, g in zip(ref, got):
                e = float(np.max(np.abs(r.astype(np.float64)
                                        - g.astype(np.float64)))) \
                    if r.size else 0.0
                scale = float(np.max(np.abs(r))) if r.size else 1.0
                errs.append(e)
                ok = ok and e <= 1e-3 * max(1.0, scale)
            entry.update(ok=bool(ok), max_abs_err=max(errs) if errs else 0.0,
                         seconds=round(time.time() - t0, 2))
        except Exception as e:  # noqa: BLE001 — record and continue
            entry.update(ok=False, error=f"{type(e).__name__}: {e}"[:300],
                         seconds=round(time.time() - t0, 2))
        if not entry["ok"]:
            n_fail += 1
        record["results"].append(entry)
        flush()
        print(f"{'PASS' if entry['ok'] else 'FAIL'} {name} "
              f"({entry.get('max_abs_err', 'err')})")
    record["done"] = True
    record["n_pass"] = len(cases) - n_fail
    flush()
    print(f"{record['n_pass']}/{len(cases)} passed -> {out_path}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
