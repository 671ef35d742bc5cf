"""Ask the TPU compiler for the main paths' kernels, without a chip.

The TPU compiler is installed in the test environment and compiles for a
chip that is *described*, not attached (``v5e:2x2``).  Interpret-mode
parity (tests/test_paged_attention.py, test_flash_attention.py,
test_pallas_kernels.py) says a kernel is *right*; only this says the
chip's compiler *accepts* it — block shapes that are not ``(8k, 128k)`` or
whole in their last two dims, VMEM that does not fit, a kernel that cannot
be partitioned.  Shapes are the ones ``chip_smoke.py`` drives: GPT-2-small
widths (12 heads x 64, d_model 768), the engine's default ``max_slots=4``
/ ``block_size=16`` / ``num_blocks=128`` pool, its prefill and table-width
buckets.

Nothing here touches the topology at import, in a ``skipif`` or in a
``parametrize`` argument: only one process may hold the TPU library, and
every xdist worker imports every test file.  The fixtures describe the
topology when the first test of this file runs, in the one worker that
was given the file.  A compile that passes is not a chip run.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from conftest import compile_cache_off
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

H, D, HD = 12, 64, 768            # GPT-2-small heads
NB, BS = 128, 16                  # engine default pool
SLOTS = 4                         # engine default max_slots


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip — keep it off and silent
    with compile_cache_off():
        yield


def _compile(fn, *shapes):
    """Lower + compile for the described chip; returns the HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _no_argument_copies(text, min_mb=8, but=None):
    """No ``copy`` or ``transpose`` of ``min_mb`` MB or more whose operand
    is a program ARGUMENT — a weight or a pool, straight or through its
    prefetch — in the compiled ``text``: a program reads what it is handed
    in the layout that is stored in (docs/generation.md "A weight reaches
    its product as stored").  ``but = (pattern of the argument's name,
    bytes)`` pins the one kind of copy a caller knows of and cannot
    remove; returns how many of those there are."""
    from mxnet_tpu.observability import device_scopes as ds

    found = [c for c in ds.ProgramTable(None, text).argument_copies
             if c.bytes >= min_mb << 20]
    known = [c for c in found if but and re.fullmatch(but[0], c.argument)
             and c.bytes == but[1]]
    assert found == known, [c for c in found if c not in known]
    return len(known)


_ARGUMENT = '%w = bf16[4096,4096]{1,0:T(8,128)(2,1)} parameter(0), ' \
    'metadata={op_name="params[\\\'l0_wq\\\']"}'
_CANNED = {
    # a weight turned round as it comes, and once more inside a fusion
    # that is handed the argument itself
    "straight": (2, f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[4096,4096]) -> bf16[4096,4096] {{
  %param_0.1 = bf16[4096,4096]{{1,0:T(8,128)(2,1)}} parameter(0)
  ROOT %copy.2 = bf16[4096,4096]{{0,1:T(8,128)(2,1)}} copy(%param_0.1)
}}

ENTRY %main.1 (w: bf16[4096,4096], x: bf16[128,4096]) -> f32[128,4096] {{
  {_ARGUMENT}
  %x = bf16[128,4096]{{1,0:T(8,128)(2,1)}} parameter(1)
  %copy.1 = bf16[4096,4096]{{0,1:T(8,128)(2,1)}} copy(%w), metadata={{op_name="params[\\'l0_wq\\']"}}
  %bitcast.1 = bf16[32,128,4096]{{2,1,0:T(8,128)(2,1)}} bitcast(%copy.1)
  %fusion.1 = bf16[4096,4096]{{0,1:T(8,128)(2,1)}} fusion(%w), kind=kLoop, calls=%fused_computation.1
  ROOT %dot.1 = f32[128,4096]{{1,0:T(8,128)}} dot(%x, %fusion.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="jit(step)/decode/layer0/attn.proj/dot_general"}}
}}
"""),
    # ... behind its prefetch: slices in flight under a ConcatBitcast
    "prefetched": (1, f"""HloModule jit_step, is_scheduled=true

ENTRY %main.1 (w: bf16[4096,4096], x: bf16[128,4096]) -> f32[128,4096] {{
  {_ARGUMENT}
  %x = bf16[128,4096]{{1,0:T(8,128)(2,1)}} parameter(1)
  %slice-start.1 = ((bf16[4096,4096]{{1,0:T(8,128)(2,1)}}), bf16[2048,4096]{{1,0:T(8,128)(2,1)S(1)}}, s32[]{{:T(128)}}) slice-start(%w), slice={{[0:2048], [0:4096]}}
  %slice-start.2 = ((bf16[4096,4096]{{1,0:T(8,128)(2,1)}}), bf16[2048,4096]{{1,0:T(8,128)(2,1)S(1)}}, s32[]{{:T(128)}}) slice-start(%w), slice={{[2048:4096], [0:4096]}}
  %slice-done.1 = bf16[2048,4096]{{1,0:T(8,128)(2,1)S(1)}} slice-done(%slice-start.1)
  %slice-done.2 = bf16[2048,4096]{{1,0:T(8,128)(2,1)S(1)}} slice-done(%slice-start.2)
  %custom-call.1 = bf16[4096,4096]{{1,0:T(8,128)(2,1)S(1)}} custom-call(%slice-done.1, %slice-done.2), custom_call_target="ConcatBitcast"
  %copy.1 = bf16[4096,4096]{{0,1:T(8,128)(2,1)S(1)}} copy(%custom-call.1), metadata={{op_name="params[\\'l0_wq\\']"}}
  ROOT %dot.1 = f32[128,4096]{{1,0:T(8,128)}} dot(%x, %copy.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="jit(step)/decode/layer0/attn.proj/dot_general"}}
}}
"""),
    # what the products RETURN may be turned round, alone or in a fusion
    # whose %param_0 is that result and no argument of the program's
    "activation": (0, f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[4096,4096]) -> f32[4096,4096] {{
  %param_0.1 = f32[4096,4096]{{1,0:T(8,128)}} parameter(0)
  ROOT %copy.2 = f32[4096,4096]{{0,1:T(8,128)}} copy(%param_0.1)
}}

ENTRY %main.1 (w: bf16[4096,4096], x: bf16[4096,4096]) -> f32[4096,4096] {{
  {_ARGUMENT}
  %x = bf16[4096,4096]{{1,0:T(8,128)(2,1)}} parameter(1)
  %dot.1 = f32[4096,4096]{{1,0:T(8,128)}} dot(%x, %w), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="jit(step)/decode/layer0/attn.proj/dot_general"}}
  %copy.1 = f32[4096,4096]{{0,1:T(8,128)}} copy(%dot.1)
  %fusion.1 = f32[4096,4096]{{0,1:T(8,128)}} fusion(%dot.1), kind=kLoop, calls=%fused_computation.1
  ROOT %add.1 = f32[4096,4096]{{0,1:T(8,128)}} add(%copy.1, %fusion.1)
}}
""")}


@pytest.mark.parametrize("case", sorted(_CANNED))
def test_no_argument_copies_counts_a_weights_copies_and_no_results(case):
    """The helper on short canned texts: an argument's copy counts —
    straight, inside a fusion the argument is an operand of, behind its
    prefetch — with its bytes, scope and the argument's name; a copy of
    what a product returned does not, nor one in a fusion whose
    ``%param_0`` is such a result."""
    from mxnet_tpu.observability import device_scopes as ds

    n, text = _CANNED[case]
    copies = ds.ProgramTable(None, text).argument_copies
    assert [(c.bytes, c.argument) for c in copies] == \
        [(4096 * 4096 * 2, "params['l0_wq']")] * n
    if n:
        assert copies[-1].name == "copy.1" \
            and copies[-1].scope == "layer0/attn.proj"
        with pytest.raises(AssertionError, match="l0_wq"):
            _no_argument_copies(text)
        assert _no_argument_copies(text, min_mb=64) == 0
        assert _no_argument_copies(
            text, but=(r"params\['l\d_wq'\]", 4096 * 4096 * 2)) == n
    else:
        assert _no_argument_copies(text, min_mb=1) == 0


LAYERS = 2                        # of the layered pool the kernels are handed


def _paged_shapes(sds, B, T, W, q_dtype, pool_dtype, heads=H, layers=LAYERS,
                  nb=NB):
    """The paged call on the WHOLE layered pool; the layer it reads is an
    operand."""
    from mxnet_tpu.ops import paged_attention as pa

    hd = heads * D
    pool = sds((layers, nb, BS, hd), pool_dtype)
    shapes = [sds((B, W), jnp.int32), sds((B,), jnp.int32),
              sds((1,), jnp.int32), sds((B, T, hd), q_dtype),
              sds((B, T), jnp.int32), pool, pool]
    if pool_dtype == jnp.int8:
        shapes += [sds((nb, heads), jnp.float32)] * 2
    fn = functools.partial(pa._paged_call.__wrapped__, n_heads=heads,
                           scale=pa.attention_scale(D), interpret=False)
    return fn, shapes


# (B, T, W): decode at max_slots over the narrowest / widest table bucket,
# a spec-verify chunk, a mid prefill bucket, and the top prefill bucket
# (max_len - 1 = 1023: not a multiple of anything, padded in the wrapper)
_PAGED_SHAPES = [(SLOTS, 1, 1), (SLOTS, 1, 64), (SLOTS, 4, 64),
                 (1, 128, 8), (1, 1023, 64)]


@pytest.mark.parametrize("q_dtype,pool_dtype", [
    (jnp.float32, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
    (jnp.float32, jnp.int8), (jnp.bfloat16, jnp.int8)],
    ids=["f32", "bf16", "f32-int8", "bf16-int8"])
@pytest.mark.parametrize("B,T,W", _PAGED_SHAPES,
                         ids=[f"B{b}-T{t}-W{w}" for b, t, w in _PAGED_SHAPES])
def test_paged_kernel_compiles(one_chip, B, T, W, q_dtype, pool_dtype):
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn, shapes = _paged_shapes(sds, B, T, W, q_dtype, pool_dtype)
    assert "tpu_custom_call" in _compile(fn, *shapes)


# the benchmark's cell (PERF.md section 4): GPT-2-large, 20 heads x 64,
# 36 layers, 1536 blocks of 16, 32 slots — the decode body's real call
# (8 pages a trip out of a 9 GB pool that stays in HBM), one prefill
# chunk, and the top prefill bucket
_CELL_SHAPES = [(32, 1, 64), (32, 1, 8), (1, 128, 8), (1, 1023, 64)]


@pytest.mark.parametrize("B,T,W", _CELL_SHAPES,
                         ids=[f"B{b}-T{t}-W{w}" for b, t, w in _CELL_SHAPES])
def test_paged_kernel_compiles_at_the_cells_shapes(one_chip, B, T, W):
    from mxnet_tpu.ops import paged_attention as pa

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn, shapes = _paged_shapes(sds, B, T, W, jnp.float32, jnp.float32,
                               heads=20, layers=36, nb=1536)
    assert pa._decode_pages(T, W, BS, 1280, jnp.float32, False) \
        == (8 if T == 1 else 0)
    text = _compile(fn, *shapes)
    assert "tpu_custom_call" in text
    # the pool is an operand of the kernel itself: nothing pool-sized,
    # layered or one layer of it, is made on the way in
    made = [ln for ln in text.splitlines()
            if re.search(r"= f32\[(36,)?1536,16,1280\]", ln)
            and " parameter(" not in ln]
    assert made == []


@pytest.mark.parametrize("mp,heads,int8", [
    (4, H, False), (4, H, True), (2, 20, False)],
    ids=["mp4-float", "mp4-int8", "mp2-large-float"])
def test_paged_sharded_compiles(topo, monkeypatch, mp, heads, int8):
    """The per-head shard_map of the kernel over the layered pool.  mp=4
    over the described 2x2 leaves 3 of 12 heads a chip: 192 lanes, one
    and a half tiles, so even T == 1 takes the chunk body there.  mp=2 of
    GPT-2-large's 20 heads leaves 640 lanes: the decode body."""
    from mxnet_tpu.ops import paged_attention as pa

    # this process's backend is the CPU, where the kernels default to the
    # interpreter: ask for the native lowering the chip would get
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    mesh = Mesh(topo.devices[:mp], ("mp",))
    sds = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, spec))
    pool_dt = jnp.int8 if int8 else jnp.float32
    hd = heads * D
    assert bool(pa._decode_pages(1, 64, BS, hd // mp, pool_dt, int8)) \
        == (mp == 2)
    pool = sds((LAYERS, NB, BS, hd), pool_dt, P(None, None, None, "mp"))
    shapes = [sds((SLOTS, 1, heads, D), jnp.float32,
                  P(None, None, "mp", None)), pool, pool,
              sds((SLOTS, 64), jnp.int32, P()),
              sds((SLOTS, 1), jnp.int32, P()),
              sds((SLOTS,), jnp.int32, P())]
    if int8:
        shapes += [sds((NB, heads), jnp.float32, P(None, "mp"))] * 2

    def fn(q, k, v, t, p, m, ks=None, vs=None):
        return pa.paged_attention_sharded(q, k, v, t, p, m, mesh=mesh,
                                          k_scale=ks, v_scale=vs,
                                          layer=LAYERS - 1)

    assert "tpu_custom_call" in _compile(fn, *shapes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["fwd", "fwd_lse", "bwd"])
def test_flash_kernel_compiles(one_chip, kind, dtype):
    """(B*H, T, D) = (12, 1024, 64): one GPT-2-small sequence."""
    from mxnet_tpu.ops import flash_attention as fa

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bh, t = H, 1024
    bq, bk = fa.select_flash_blocks(D, dtype)
    x = sds((bh, t, D), dtype)
    col = sds((bh, t, 1), jnp.float32)
    kw = dict(t_real=t, causal=True, bq=bq, bk=bk, scale=0.125,
              interpret=False)
    if kind == "bwd":
        fn = functools.partial(fa._bwd_call.__wrapped__, **kw)
        shapes = [x, x, x, x, col, col]
    else:
        fn = functools.partial(fa._fwd_call.__wrapped__,
                               with_lse=kind == "fwd_lse", **kw)
        shapes = [x, x, x]
    assert "tpu_custom_call" in _compile(fn, *shapes)


@pytest.mark.parametrize("rows", [SLOTS, 128, 1023],
                         ids=["decode", "prefill128", "prefill1023"])
def test_layer_norm_compiles(one_chip, monkeypatch, rows):
    """Fused LayerNorm at the decode row count (4: padded to a legal block
    in the wrapper, not swapped for the reference) and prefill buckets."""
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = lambda x, g, b: pk._ln_fused_fwd(x, g, b, 1e-5, False)[0]
    text = _compile(fn, sds((rows, HD), jnp.float32),
                    sds((HD,), jnp.float32), sds((HD,), jnp.float32))
    assert "tpu_custom_call" in text


def test_batch_norm_kernels_compile(one_chip, monkeypatch):
    """BN stats + normalize (opt-in via MXTPU_BN_PALLAS, channels-minor):
    a ResNet-50 stage-1 activation, batch 32 x 56 x 56 rows of 256."""
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = lambda x, g, b: pk._bn_fused_fwd(x, g, b, 1e-5, 3)[0]
    text = _compile(fn, sds((32, 56, 56, 256), jnp.bfloat16),
                    sds((256,), jnp.float32), sds((256,), jnp.float32))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["float", "int8"])
def test_decode_step_program_compiles(one_chip, monkeypatch, kv_dtype):
    """The whole model step the engine dispatches every decode iteration
    (transformer_lm_decode, paged kernel + fused LN pinned on as they are
    on a TPU backend) at GPT-2-small widths; depth cut to 2 layers to keep
    the compile short — layers repeat the same program."""
    from mxnet_tpu.parallel import transformer as tr

    monkeypatch.setenv("TPUMX_PALLAS", "1")
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    cfg = tr.TransformerConfig(vocab=50257, d_model=HD, n_heads=H,
                               n_layers=2, d_ff=3072, max_len=1024)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: tr.transformer_lm_init(
            cfg, jax.random.PRNGKey(0))))
    pool_dt = jnp.int8 if kv_dtype else jnp.float32
    pool = sds((cfg.n_layers, NB, BS, HD), pool_dt)
    pools = (pool, pool)
    if kv_dtype:
        sc = sds((cfg.n_layers, NB, H), jnp.float32)
        pools += (sc, sc)
    shapes = [params, sds((SLOTS, 1), jnp.int32), sds((SLOTS, 1), jnp.int32),
              sds((SLOTS,), jnp.int32), pools, sds((SLOTS, 64), jnp.int32)]

    # through the engine's model seam (serving/generation/programs.py):
    # GPT-2's block is its first model, and compiles to the same program
    model = tr.TransformerLM(cfg)

    def step(params, tokens, positions, lengths, pools, tables):
        return model.step(params, tokens, positions, lengths, pools,
                          tables, attention_kernel="paged")[:2]

    text = jax.jit(step, donate_argnums=(4,)).lower(
        *shapes).compile().as_text()
    # the kernel's names in a device trace are the parent's
    assert "_paged_call_w64_decode" in text
    assert not re.search(r"_paged_call_w\d+_t\d+_", text)
    # per layer: one paged-attention call and two LayerNorm calls, plus
    # the final LayerNorm
    assert text.count("tpu_custom_call") >= 3 * cfg.n_layers + 1
    # the donated pools are updated in place and read in place: the only
    # pool-sized results are the scatters into the whole pool — never one
    # layer of it, never a copy (PERF.md, PR 25: the parent's program
    # copied each layer's slice for its kernel call, 9 GB a step)
    dt = "s8" if kv_dtype else "f32"
    one_layer = f"= {dt}[{NB},{BS},{HD}]"
    whole = f"= {dt}[{cfg.n_layers},{NB},{BS},{HD}]"
    assert one_layer not in text
    made = {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
            for ln in text.splitlines() if whole in ln}
    # (copy-start / copy-done: the compiler moving this test's 3 MB pool
    # into fast memory whole, not a copy of it in HBM)
    assert made <= {"parameter", "scatter", "fusion", "copy-start",
                    "copy-done"}, made


@pytest.mark.parametrize("slots", [32, 128, 256])
def test_first_token_programs_compile_at_the_cells_slots(one_chip, slots):
    """The two slot-sized programs with no model in them through which a
    decode step is fed what is still on the device — a prefill's first
    token placed at its row (the slot an operand: one program whatever
    the number of rows a pass admits), then the merge with the host's
    tokens — at the serving cells' slot counts: an update in place of a
    dynamic slice and a select, no gather, scatter or loop."""
    from mxnet_tpu.serving.generation import programs as gp

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = sds((slots,), jnp.int32)
    one = sds((1,), jnp.int32)
    placed = _compile(gp._place_first, i32, one, one)
    assert "dynamic-update-slice" in placed
    carried = _compile(gp._carry, i32, sds((slots, 1), jnp.int32),
                       sds((slots,), jnp.bool_))
    for text in (placed, carried):
        assert not re.search(r"\b(gather|scatter|while|custom-call)\(",
                             text)


def test_decode_step_program_compiles_mp4(topo, monkeypatch):
    """The same step as a GSPMD program over ``mp=4`` (GenerationConfig(
    mp_devices=4)): params placed by the transformer's partition rules,
    the pool sharded on its folded minor dim.  The compiler cannot
    partition an opaque Mosaic call, so every kernel in the program must
    sit inside a shard_map — the paged kernel per head, fused LayerNorm
    over the replicated activations (chip_smoke.py --chips 4 first met
    this on the chip, PR 21)."""
    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.parallel.partition_rules import make_param_specs

    monkeypatch.setenv("TPUMX_PALLAS", "1")
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    cfg = tr.TransformerConfig(vocab=50257, d_model=HD, n_heads=H,
                               n_layers=2, d_ff=3072, max_len=1024)
    mesh = Mesh(topo.devices, ("mp",))
    sds = lambda shape, dt, *spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P(*spec)))
    shapes = jax.eval_shape(lambda: tr.transformer_lm_init(
        cfg, jax.random.PRNGKey(0)))
    specs = make_param_specs(tr.transformer_partition_rules(),
                             {k: v.shape for k, v in shapes.items()}, mesh)
    params = {k: sds(v.shape, v.dtype, *specs.get(k, ()))
              for k, v in shapes.items()}
    pool = sds((cfg.n_layers, NB, BS, HD), jnp.float32,
               None, None, None, "mp")
    args = [params, sds((SLOTS, 1), jnp.int32), sds((SLOTS, 1), jnp.int32),
            sds((SLOTS,), jnp.int32), pool, pool,
            sds((SLOTS, 64), jnp.int32)]

    def step(params, tokens, positions, lengths, kp, vp, tables):
        return tr.transformer_lm_decode(params, tokens, positions, lengths,
                                        kp, vp, tables, cfg,
                                        attention_kernel="paged",
                                        mp_mesh=mesh)

    text = jax.jit(step, donate_argnums=(4, 5)).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3 * cfg.n_layers + 1
    assert "all-reduce" in text     # the row-parallel projections' psum


@pytest.mark.parametrize("mp", [1, 4])
def test_sampling_step_program_compiles(topo, one_chip, monkeypatch, mp):
    """The decode program as the engine dispatches it, the sampler behind
    the model step (``programs._model_step``): the sampler's three bodies
    are one conditional, and the program's only sort over the vocabulary
    sits inside a branch of it — on one chip, and as a GSPMD program over
    ``mp=4`` whose head is sharded over the vocabulary (the branch index
    comes from replicated ``(S,)`` arrays: every shard takes the same
    body)."""
    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.parallel.partition_rules import make_param_specs
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS", "1")
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    cfg = tr.TransformerConfig(vocab=50257, d_model=HD, n_heads=H,
                               n_layers=2, d_ff=3072, max_len=1024)
    shapes = jax.eval_shape(lambda: tr.transformer_lm_init(
        cfg, jax.random.PRNGKey(0)))
    if mp == 1:
        mesh = None
        sds = lambda shape, dt, *spec: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        specs = {}
    else:
        mesh = Mesh(topo.devices, ("mp",))
        sds = lambda shape, dt, *spec: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=NamedSharding(mesh, P(*spec)))
        specs = make_param_specs(tr.transformer_partition_rules(),
                                 {k: v.shape for k, v in shapes.items()},
                                 mesh)
        assert "mp" in tuple(specs["tok_emb"])      # the head's vocabulary
    params = {k: sds(v.shape, v.dtype, *specs.get(k, ()))
              for k, v in shapes.items()}
    pool = sds((cfg.n_layers, NB, BS, HD), jnp.float32,
               None, None, None, "mp")
    row = lambda dt: sds((SLOTS,), dt)  # noqa: E731
    args = [params, (pool, pool), sds((SLOTS, 1), jnp.int32),
            sds((SLOTS, 1), jnp.int32), row(jnp.int32),
            sds((SLOTS, 64), jnp.int32), row(jnp.uint32), row(jnp.uint32),
            row(jnp.float32), row(jnp.int32), row(jnp.float32)]
    step = functools.partial(gp._model_step, model=tr.TransformerLM(cfg),
                             attention_kernel="paged", mp_mesh=mesh)
    text = jax.jit(step, donate_argnums=(1,)).lower(
        *args).compile().as_text()
    assert len(re.findall(r" conditional\(", text)) == 1
    sorts = [ln for ln in text.splitlines() if re.search(r" sort\(", ln)]
    assert len(sorts) == 1 and "cond/branch_2_fun" in sorts[0]
    entry = text[text.index("\nENTRY "):]
    assert not re.search(r" sort\(", entry)


# the sdar-30b-a3b configuration's cell (PERF.md section 4): hidden 2048,
# 32 query heads over 4 KV heads of 128, 128 experts of 768 top-8,
# vocabulary 151,936, 6 layers, bfloat16 parameters and a bfloat16 pool of
# 4608 blocks of 16 folded 512 wide, 64 slots, blocks of 4
def _sdar_cell(sds, n_layers):
    from mxnet_tpu.parallel import sdar_moe as sm

    cfg = sm.SdarMoeConfig(num_hidden_layers=n_layers)
    model = sm.SdarMoeLM(cfg, max_len=4096)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: sm.sdar_moe_init(cfg, jax.random.PRNGKey(0),
                                                jnp.bfloat16)))
    pool = sds((n_layers, 4608, BS, 512), jnp.bfloat16)
    return model, params, pool


# what may produce a pool-sized value: the donated parameter, the scatters
# into it (alone, fused, or as an update of a reshaped view) — never a copy
_IN_PLACE = {"parameter", "scatter", "fusion", "bitcast",
             "dynamic-update-slice", "copy-start", "copy-done"}


def _pool_makers(text, n_layers):
    whole = f"= bf16[{n_layers},4608,{BS},512]"
    assert f"= bf16[4608,{BS},512]" not in text     # never one layer of it
    return {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
            for ln in text.splitlines() if whole in ln}


@pytest.mark.parametrize("M,K,N", [(2048, 2048, 768), (2048, 768, 2048),
                                   (16384, 2048, 768), (16384, 768, 2048)])
def test_grouped_matmul_kernel_compiles_at_the_cells_shapes(one_chip, M, K,
                                                            N):
    """The expert products of a block step (64 rows x 4 x top-8) and of a
    2048-token prefill chunk: a whole expert matrix a grid step."""
    from mxnet_tpu.ops import grouped_matmul as gm

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compile(
        functools.partial(gm._gmm_call.__wrapped__, interpret=False),
        sds((M, K), jnp.bfloat16), sds((128, K, N), jnp.bfloat16),
        sds((128,), jnp.int32))
    assert "tpu_custom_call" in text and "_gmm_call" in text


def test_block_step_program_compiles_at_the_cells_shapes(one_chip,
                                                         monkeypatch):
    """``gen_block`` as the service dispatches it at the cell's widest
    table: the chip's compiler accepts the grouped-head chunk body on the
    whole layered pool, the grouped expert products stay grouped (no
    dense ``(tokens, experts, width)`` product), and the program fits."""
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    n_layers, S, L, W = 6, 64, 4, 256
    model, params, pool = _sdar_cell(sds, n_layers)
    fn = jax.jit(functools.partial(gp._block_step, model=model,
                                   attention_kernel="paged"),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, (pool, pool), sds((S, L), jnp.int32), sds((S, L), jnp.int32),
        sds((S,), jnp.int32), sds((S, W), jnp.int32), sds((S, L), jnp.bool_),
        sds((S,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("_paged_call_w256_t4_block") >= n_layers
    assert not re.search(r"_paged_call_w\d+_(decode|t\d+_prefill)", text)
    # three grouped products a layer, each a kernel
    assert len(re.findall(r"%_gmm_call[\w.\-]* = f32\[2048,", text)) \
        == 3 * n_layers
    assert " sort(" in text and "f32[256,128,768]" not in text
    assert _pool_makers(text, n_layers) <= _IN_PLACE
    # the logits go back position-major, as the head's product wrote them
    # and the sampler read them: the program's third result is that
    # product's, and nothing of the logits' size (155 MB) is copied
    _no_argument_copies(text)
    logits = r"f32\[(64,4|4,64|256),151936\]"
    assert re.search(rf"ROOT %tuple[\w.]* = \(.*f32\[4,64,151936\]", text)
    assert not re.search(rf"= {logits}\S* (copy|transpose)\(", text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes < 13e9


def test_block_step_text_resolves_to_the_programs_scopes(one_chip,
                                                         monkeypatch):
    """What the CHIP's compiler writes is what the device-scope resolver
    reads (docs/observability.md "Device scopes"): of the instructions
    of the block step's entry computation that a trace would show, all but
    a few copies resolve to a scope, the program's kind is its outermost
    scope, and the kernels sit under the layer parts that call them."""
    from mxnet_tpu.observability import device_scopes as ds
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    S, L, W = 64, 4, 256
    model, params, pool = _sdar_cell(sds, 2)
    lowered = jax.jit(functools.partial(
        gp._block_step, model=model, attention_kernel="paged"),
        donate_argnums=(1,)).lower(
        params, (pool, pool), sds((S, L), jnp.int32), sds((S, L), jnp.int32),
        sds((S,), jnp.int32), sds((S, W), jnp.int32), sds((S, L), jnp.bool_),
        sds((S,), jnp.int32))
    text = lowered.compile().as_text()
    # this build's names, by the resolver's own test; and the chip's
    # compiler takes the option a stale text is compiled again with
    assert not ds.stale(lowered, text)
    assert "fusion" in jax.jit(lambda x: jnp.tanh(x) * 2).lower(
        sds((256, 256), jnp.float32)).compile(compiler_options={
            "xla_dump_disable_metadata": False}).as_text()
    table = ds.ProgramTable(None, text)
    assert table.kind == "block"
    shown = [n for n in table.order if re.search(
        rf"%{re.escape(n)} = .*[\]\}}\)] (fusion|custom-call|copy|copy-start|"
        r"copy-done|slice-start|slice-done|sort|while)\(", text)]
    named = [n for n in shown if table.instrs[n][1] is not None]
    assert len(shown) > 100 and len(named) >= 0.95 * len(shown)
    scopes = {table.instrs[n][1].scope for n in named}
    assert {"layer0/moe.experts/_gmm_call", "layer1/attn.cache_write",
            "layer0/attn.kernel/_paged_call_w256_t4_block", "sample",
            "head"} <= scopes
    # a trace's whole HLO text of an instruction finds it too
    gmm = next(ln for ln in text.splitlines() if "%_gmm_call" in ln
               and " custom-call(" in ln)
    event = gmm.strip().split(", metadata={")[0]
    assert ds.Table([table]).resolve(event).scope.endswith(
        "moe.experts/_gmm_call")


@pytest.mark.parametrize("T,W", [(512, 32), (2048, 256)])
def test_block_prefill_program_compiles_at_the_cells_shapes(one_chip,
                                                            monkeypatch,
                                                            T, W):
    """The cache-filling prefill of the cell's two chunk lengths (no
    head); depth 2: layers repeat the same program."""
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model, params, pool = _sdar_cell(sds, 2)
    fn = jax.jit(functools.partial(gp._block_fill, model=model,
                                   attention_kernel="paged"),
                 donate_argnums=(1,))
    text = fn.lower(params, (pool, pool), sds((1, T), jnp.int32),
                    sds((1, T), jnp.int32), sds((1,), jnp.int32),
                    sds((1, W), jnp.int32)).compile().as_text()
    assert text.count(f"_paged_call_w{W}_t{T}_prefill") >= 2
    assert "151936]" not in text.replace("bf16[151936,2048]", "")  # no head
    assert _pool_makers(text, 2) <= _IN_PLACE


# the dots-vlm1 configuration's cell (PERF.md section 4): hidden 7168, 128
# heads, latent 512 + 64 cached 640 wide, 16 held experts of 2048 of the
# router's 256, bfloat16 parameters and a bfloat16 latent pool, 256 slots,
# ONE table width of 512 blocks of 16, prefill chunks of 512
def _latent_cell(sds, n_layers, num_blocks=4096):
    from mxnet_tpu.parallel import latent_moe as lm

    cfg = lm.LatentMoeConfig(vocab_size=16160, num_hidden_layers=n_layers,
                             first_k_dense_replace=1)
    model = lm.LatentMoeLM(cfg, max_len=8192, experts_held=(0, 16))
    params = {k: sds(s, jnp.bfloat16) for k, s in
              lm.latent_moe_param_shapes(cfg, (0, 16)).items()}
    (_, lanes), = model.cache_spec()["pools"]
    assert lanes == 640            # 576 padded to whole 128-lane tiles
    return model, params, sds((n_layers, num_blocks, BS, lanes), jnp.bfloat16)


@pytest.mark.parametrize("B,T", [(256, 1), (1, 512), (1, 1024)],
                         ids=["decode", "prefill", "prefill1024"])
def test_latent_kernel_compiles_at_the_cells_shapes(one_chip, B, T):
    """The latent body on the whole layered pool at the cell's one table
    width (a 512 KB table in scalar memory): 128 query rows a decode tile,
    eight tokens x 128 heads a prefill tile."""
    from mxnet_tpu.ops import latent_attention as la

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compile(
        functools.partial(la._mla_call.__wrapped__, v_width=512, scale=0.1,
                          interpret=False),
        sds((B, 512), jnp.int32), sds((B,), jnp.int32), sds((1,), jnp.int32),
        sds((B, T, 128, 576), jnp.float32), sds((B, T), jnp.int32),
        sds((5, 4096, BS, 640), jnp.bfloat16))
    name = "_mla_call_w512_decode" if T == 1 \
        else f"_mla_call_w512_t{T}_prefill"
    assert "tpu_custom_call" in text and name in text


def test_latent_pool_576_wide_is_refused(one_chip):
    """Why the pool is 640 wide: the chip stores 576 lanes as 640 and its
    compiler refuses a page copy of 576 (docs/generation.md "Latent
    attention")."""
    from mxnet_tpu.ops import latent_attention as la

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(
            functools.partial(la._mla_call.__wrapped__, v_width=512,
                              scale=0.1, interpret=False),
            sds((8, 64), jnp.int32), sds((8,), jnp.int32),
            sds((1,), jnp.int32), sds((8, 1, 128, 576), jnp.float32),
            sds((8, 1), jnp.int32), sds((1, 256, BS, 576), jnp.bfloat16))


@pytest.mark.parametrize("M,K,N", [(2048, 7168, 2048), (2048, 2048, 7168),
                                   (4096, 7168, 2048), (4096, 2048, 7168)])
def test_tiled_grouped_matmul_compiles_at_the_cells_shapes(one_chip, M, K, N):
    """The held experts' products of a decode step (256 rows x top-8) and
    of a 512-token prefill chunk: 29 MB a matrix, a column tile a grid
    step."""
    from mxnet_tpu.ops import grouped_matmul as gm

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compile(
        functools.partial(gm._gmm_call.__wrapped__, interpret=False),
        sds((M, K), jnp.bfloat16), sds((16, K, N), jnp.bfloat16),
        sds((16,), jnp.int32))
    assert "tpu_custom_call" in text and "_gmm_call" in text


@pytest.mark.parametrize("S,T", [(256, 1), (1, 512), (1, 1024)],
                         ids=["decode", "prefill", "prefill1024"])
def test_latent_decode_step_program_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, S, T):
    """``gen_decode`` (256 rows) and ``gen_prefill`` (a 512-token chunk and
    the model's longest, 1,024) as
    the service dispatches them (one width), at depth 2 (one dense and one
    expert layer: layers repeat): the latent pool is updated in place, the
    kernels are there, the counts come back, and ``wqb`` reaches its
    product as stored."""
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model, params, pool = _latent_cell(sds, 2)
    W = 512
    fn = jax.jit(functools.partial(gp._model_step, model=model,
                                   attention_kernel="paged"),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, (pool,), sds((S, T), jnp.int32), sds((S, T), jnp.int32),
        sds((S,), jnp.int32), sds((S, W), jnp.int32), sds((S,), jnp.uint32),
        sds((S,), jnp.uint32), sds((S,), jnp.float32), sds((S,), jnp.int32),
        sds((S,), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("_mla_call_w512_decode" if T == 1
                      else f"_mla_call_w512_t{T}_prefill") >= 2
    # 16 of the router's 256 experts held: the expert layer walks the held
    # rows in tiles of 2 x 256 x 8 / 16 = 256 (512 a chunk), ONE copy of
    # the kernel's call a projection inside the loop, none over all 2,048
    # (4,096) rows, and the experts' matrices go through the loop as they
    # are (1.4 GB a layer)
    rows = 2 * S * T * 8 // 16
    assert len(re.findall(rf"%_gmm_call[\w.\-]* = f32\[{rows},", text)) == 3
    assert f"f32[{S * T * 8},7168]" not in text
    _the_walk_is_named_and_copies_no_expert(text, 7168, 2048)
    # no weight is turned round but ONE: the absorbed products are batched
    # over the heads, the chip's compiler wants a batched product's
    # operands head-major, and ``wkvb`` is stored ``(c, heads x 256)`` —
    # latent-major, its heads inside the lanes: no layout of the stored
    # bytes has the heads outside, so a layer copies its 33.5 MB at most
    # once a call for both products (ROADMAP S16 keeps it; at the cell's
    # depth of 5 the chip's own text has 4 in the chunk's program and none
    # in the decode step's: PERF.md PR 39)
    assert _no_argument_copies(
        text, but=(r"params\['l\d_wkvb'\]", 512 * 128 * 256 * 2)) <= 2
    whole = f"= bf16[2,4096,{BS},640]"
    makers = {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
              for ln in text.splitlines() if whole in ln}
    assert makers <= _IN_PLACE
    mem = compiled.memory_analysis()
    print(f"latent S={S} T={T} temp {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < (1.0e9 if T == 1024 else 0.5e9)


def _the_walk_is_named_and_copies_no_expert(text, d, f):
    """The program's ``while`` over the held rows' tiles: its body's
    operations resolve to the expert layer's scopes (the loop is jax's
    structure, not a scope), and nothing of an expert matrix's size is
    copied on the way in."""
    from mxnet_tpu.observability import device_scopes as ds

    table = ds.ProgramTable(None, text)
    scopes = {r.scope for _, r in table.instrs.values() if r is not None}
    assert {"layer1/moe.experts/_gmm_call", "layer1/moe.combine",
            "layer1/moe.route"} <= scopes
    assert not [s for s in scopes if "while" in s or "body" in s]
    experts = rf"bf16\[16,({d},{f}|{f},{d})\]"
    assert re.search(experts, text)
    assert not re.search(rf"= {experts}\S* (copy|fusion)\(", text)


# -- MiMo-V2.5's window and full attention layers (parallel/hybrid_moe.py) ----
# kind: (KV heads, query heads a KV head, K lanes, V lanes, block, window,
# sink, table width or None for the ring)
_TILES = dict(full=(4, 16, 192, 128, BS, 0, False, 1024),
              window=(8, 8, 192, 128, BS, 128, True, None),
              # phi-4-mini-flash's window layers by way of
              # ops/diff_attention.py: a KV pair is one head of 128 lanes
              swa=(10, 4, 128, 128, 32, 512, False, None),
              # trinity-mini's two kinds: 4 KV heads of 128 lanes, 8 query
              # heads each, blocks of 32, a window of 2,048
              afm_full=(4, 8, 128, 128, 32, 0, False, 1024),
              afm_window=(4, 8, 128, 128, 32, 2048, False, None))


@pytest.mark.parametrize("kind,B,T", [
    ("full", 128, 1), ("window", 128, 1), ("full", 1, 512),
    ("window", 1, 512), ("swa", 128, 1), ("afm_full", 64, 1),
    ("afm_window", 64, 1), ("afm_full", 1, 512), ("afm_window", 1, 512),
    ("full", 1, 1024), ("window", 1, 1024), ("afm_full", 1, 1024),
    ("afm_window", 1, 1024)],
    ids=["full-decode", "window-decode", "full-prefill", "window-prefill",
         "swa-decode", "afm-full-decode", "afm-window-decode",
         "afm-full-prefill", "afm-window-prefill", "full-prefill1024",
         "window-prefill1024", "afm-full-prefill1024",
         "afm-window-prefill1024"])
def test_tiles_body_compiles_at_the_cells_shapes(one_chip, kind, B, T):
    """The tiles body at ``mimo-v2.5``'s published shapes: ``Hkv x 192``
    K pages beside ``Hkv x 128`` V pages, 16 (full) and 8 (window) query
    heads a KV head as rows, the full kind's table 1,024 wide (a 512 KB
    table in scalar memory) and the window kind's a ring of 16 (decode) or
    64 (a 512-token chunk), with the window and the sink; and at
    ``phi-4-mini-flash``'s swa decode call: 10 pairs of 128 lanes, blocks
    and a ring of 32, a window of 512, so a trip of 17 pages (PR 46); and
    at ``trinity-mini``'s: a window of 2,048 over blocks of 32 reaches 65
    pages of 64 KB, 8.5 MB double-buffered, which a decode tile's 8 query
    rows leave room for: ONE trip a row on a ring of 128 columns, computed
    over the 16-page parts a row's pages fill (PR 48; five trips of 16
    before); its 512-token
    chunk's 256-row tile reaches 73 and keeps 16 pages a trip (PR 47).  A
    chunk of 1,024, the model's longest (PR 50), has the same tiles: 256
    query rows whatever the chunk, a ring of 128 columns on both cells."""
    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.serving.generation.kv_cache import ring_width

    hkv, G, dk, dv, bs, window, sink, width = _TILES[kind]
    W = width or ring_width(window, T, bs)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    shapes = [sds((B, W), jnp.int32), sds((B,), jnp.int32),
              sds((1,), jnp.int32), sds((B, G * T, hkv * dk), jnp.float32),
              sds((B, G * T), jnp.int32),
              sds((2, 2048, bs, hkv * dk), jnp.bfloat16),
              sds((2, 2048, bs, hkv * dv), jnp.bfloat16)]
    if sink:
        shapes.append(sds((G * T, hkv), jnp.float32))
    if kind == "swa":
        assert (W, pa._tiles_geometry(G * T, G, bs, W, window, pa._page_bytes(
            *shapes[5:7]))) == (32, (8, 17))
    if kind == "afm_window":
        assert (W, pa._tiles_geometry(G * T, G, bs, W, window, pa._page_bytes(
            *shapes[5:7]))) == (128, (8, 65) if T == 1 else (256, 16))
    phase = "decode" if T == 1 else "prefill"
    text = _compile(
        functools.partial(pa._tiles_call.__wrapped__, n_heads=hkv,
                          scale=dk ** -0.5, interpret=False, groups=G,
                          call=f"{kind}_{phase}", window=window), *shapes)
    assert "tpu_custom_call" in text
    assert f"_paged_call_w{W}_t{T}_{kind}_{phase}" in text


def _hybrid_cell(sds, n_layers=3):
    from mxnet_tpu.parallel import hybrid_moe as hm

    cfg = hm.HybridMoeConfig(
        vocab_size=19072, num_hidden_layers=n_layers,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1)[:n_layers],
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1)[:n_layers])
    model = hm.HybridMoeLM(cfg, max_len=16384, experts_held=(0, 16))
    params = {k: sds(s, jnp.bfloat16) for k, s in
              hm.hybrid_moe_param_shapes(cfg, (0, 16)).items()}
    return model, params


@pytest.mark.parametrize("S,T,ring", [(128, 1, 16), (1, 512, 64),
                                      (1, 1024, 128)],
                         ids=["decode", "prefill", "prefill1024"])
def test_hybrid_step_program_compiles_at_the_cells_shapes(one_chip,
                                                          monkeypatch, S, T,
                                                          ring):
    """``gen_decode`` (128 rows) and ``gen_prefill`` (a 512-token chunk and
    the model's longest, 1,024, on a ring twice as wide) as
    the service dispatches them, at depth 3 (the dense layer with full
    attention and two window expert layers: layers repeat): both kinds'
    pools are updated in place, a table a kind, every attention call is
    named for its kind and phase, the counts come back."""
    from mxnet_tpu.serving.generation import programs as gp
    from mxnet_tpu.serving.generation.kv_cache import window_blocks

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model, params = _hybrid_cell(sds)
    full, window = model.cache_spec()["kinds"]
    assert [w for _, w in full["pools"]] == [768, 512]
    assert [w for _, w in window["pools"]] == [1536, 1024]
    # the window kind as the cell holds it: 128 slots at rest and the one
    # row being prefilled in the model's longest chunks
    held = 1 + 128 * window_blocks(128, 1, BS) \
        + window_blocks(128, model.longest_chunk, BS)
    assert held == 1354
    pools = tuple(sds((k["n_layers"], nb, BS, w), jnp.bfloat16)
                  for k, nb in ((full, 4096), (window, held))
                  for _, w in k["pools"])
    fn = jax.jit(functools.partial(gp._model_step, model=model,
                                   attention_kernel="paged"),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, pools, sds((S, T), jnp.int32), sds((S, T), jnp.int32),
        sds((S,), jnp.int32),
        (sds((S, 1024), jnp.int32), sds((S, ring), jnp.int32)),
        sds((S,), jnp.uint32), sds((S,), jnp.uint32), sds((S,), jnp.float32),
        sds((S,), jnp.int32), sds((S,), jnp.float32)).compile()
    text = compiled.as_text()
    phase = "decode" if T == 1 else "prefill"
    assert f"_paged_call_w1024_t{T}_full_{phase}" in text
    assert text.count(f"_paged_call_w{ring}_t{T}_window_{phase}") >= 2
    # the held rows' tiles: 2 x 128 x 8 / 16 = 128 rows a decode step,
    # 512 a chunk, and never all S x T x 8 of them
    rows = 2 * S * T * 8 // 16
    assert len(re.findall(rf"%_gmm_call[\w.\-]* = f32\[{rows},", text)) == 6
    assert f"f32[{S * T * 8},4096]" not in text
    _the_walk_is_named_and_copies_no_expert(text, 4096, 2048)
    for shape in (f"= bf16[1,4096,{BS},768]", f"= bf16[2,{held},{BS},1536]"):
        makers = {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
                  for ln in text.splitlines() if shape in ln}
        assert makers <= _IN_PLACE, (shape, makers)
    # every weight reaches its product as stored: ``wq`` (100 MB a layer)
    # and ``wk`` are multiplied flat and the RESULT is cut into heads of 192
    _no_argument_copies(text)
    mem = compiled.memory_analysis()
    print(f"hybrid S={S} T={T} temp {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < 1.0e9


def _afmoe_cell(sds):
    """``trinity-mini`` at depth 4: both dense layers (window), a window
    and a full expert layer, 16 of 128 experts held, the vocabulary
    whole."""
    from mxnet_tpu.parallel import hybrid_moe as hm
    from perfbench import harness

    c = harness.load_json("configs", "trinity-mini.json")
    cfg = hm.HybridMoeConfig.from_afmoe(
        dict(c, num_hidden_layers=4, layer_types=c["layer_types"][:4]),
        n_routed_experts=c["published"]["num_experts"])
    held = tuple(c["experts_held"])
    model = hm.HybridMoeLM(cfg, max_len=c["max_len"], experts_held=held)
    params = {k: sds(s, jnp.bfloat16) for k, s in
              hm.hybrid_moe_param_shapes(cfg, held).items()}
    return model, params, c["service"]


@pytest.mark.parametrize("T", [1, 512, 1024],
                         ids=["decode", "prefill", "prefill1024"])
def test_afmoe_step_program_compiles_at_the_cells_shapes(one_chip,
                                                         monkeypatch, T):
    """``trinity-mini``'s ``gen_decode`` (64 rows) and ``gen_prefill`` (a
    512-token chunk and the model's longest, 1,024) as the service
    dispatches them, at depth 4: both
    kinds' calls take the tiles body and are named for kind and phase, the
    window kind's on a ring of 128 columns; the pools of the cell's own
    sizes (16,000 and 4,322 blocks of 32) are updated in place; the held
    rows' tiles are 2 x S x T x 8 / 8 rows; the temporaries of a chunk
    over the whole vocabulary's head stay under 1.5 GB."""
    from mxnet_tpu.serving.generation import programs as gp
    from mxnet_tpu.serving.generation.kv_cache import (ring_width,
                                                       window_blocks)

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model, params, service = _afmoe_cell(sds)
    S = service["max_slots"] if T == 1 else 1
    bs = service["block_size"]
    full, window = model.cache_spec()["kinds"]
    held = 1 + service["max_slots"] * window_blocks(2048, 1, bs) \
        + window_blocks(2048, model.longest_chunk, bs)
    assert held == 4322 and ring_width(2048, T, bs) == 128
    pools = tuple(sds((k["n_layers"], nb, bs, w), jnp.bfloat16)
                  for k, nb in ((full, service["num_blocks"]), (window, held))
                  for _, w in k["pools"])
    fn = jax.jit(functools.partial(gp._model_step, model=model,
                                   attention_kernel="paged"),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, pools, sds((S, T), jnp.int32), sds((S, T), jnp.int32),
        sds((S,), jnp.int32),
        (sds((S, 1024), jnp.int32), sds((S, 128), jnp.int32)),
        sds((S,), jnp.uint32), sds((S,), jnp.uint32), sds((S,), jnp.float32),
        sds((S,), jnp.int32), sds((S,), jnp.float32)).compile()
    text = compiled.as_text()
    phase = "decode" if T == 1 else "prefill"
    assert f"_paged_call_w1024_t{T}_full_{phase}" in text
    assert text.count(f"_paged_call_w128_t{T}_window_{phase}") >= 3
    rows = 2 * S * T * 8 // 8
    assert len(re.findall(rf"%_gmm_call[\w.\-]* = f32\[{rows},", text)) == 6
    for shape in (f"= bf16[1,{service['num_blocks']},{bs},512]",
                  f"= bf16[3,{held},{bs},512]"):
        makers = {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
                  for ln in text.splitlines() if shape in ln}
        assert makers <= _IN_PLACE, (shape, makers)
    _no_argument_copies(text)
    mem = compiled.memory_analysis()
    print(f"afmoe T={T} temp {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < 1.5e9


# -- brumby-14b: power retention over a slot's state (PR 40) ----------------

def _retention_cell(sds, n_layers=2):
    from mxnet_tpu.parallel import retention_lm as rl

    cfg = rl.RetentionConfig(num_hidden_layers=n_layers)
    model = rl.RetentionLM(cfg, max_len=32768)
    params = {k: sds(s, jnp.bfloat16)
              for k, s in rl.retention_param_shapes(cfg).items()}
    (kind,) = model.cache_spec()["kinds"]
    pools = tuple(sds((n_layers, 25) + shape, jnp.float32)
                  for _, shape in kind["state"])
    return model, params, pools


@pytest.mark.parametrize("B,T", [(24, 1), (1, 128), (1, 512)],
                         ids=["decode", "prefill128", "prefill512"])
def test_retention_kernels_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                       B, T):
    """``_ret_call_decode`` (24 rows x 8 KV heads, a head's state of 65 x
    128 x 128 float32 a block) and ``_ret_call_t<T>_prefill`` (a chunk of
    the 5 query heads of a KV head) at ``brumby-14b``'s widths: the pools
    are updated in place, never copied."""
    from mxnet_tpu.ops import retention as rt

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pools = tuple(sds((6, 25) + s, jnp.float32)
                  for _, s in rt.state_shapes(8, 128, 128))
    assert [p.shape for p in pools] == [(6, 25, 8, 65, 136, 128)]
    f32 = jnp.float32

    def fn(q, k, v, g, fresh, pool, slots):
        return rt.retention(q, k, v, g, fresh, pool, slots, layer=3,
                            eps=1e-6, kernel=True)

    text = jax.jit(fn, donate_argnums=(5,)).lower(
        sds((B, T, 40, 128), f32), sds((B, T, 8, 128), f32),
        sds((B, T, 8, 128), f32), sds((B, T, 8), f32), sds((B,), jnp.bool_),
        *pools, sds((B,), jnp.int32)).compile().as_text()
    assert ("_ret_call_decode" if T == 1 else f"_ret_call_t{T}_prefill") \
        in text
    assert not [ln for ln in text.splitlines()
                if "f32[6,25,8,65,136" in ln and " copy(" in ln]


@pytest.mark.parametrize("S,T", [(24, 1), (1, 128), (1, 512)],
                         ids=["decode", "prefill128", "prefill512"])
def test_retention_step_program_compiles_at_the_cells_shapes(one_chip,
                                                             monkeypatch, S,
                                                             T):
    """The cell's three programs as the service dispatches them, at depth
    2 (layers repeat): ``gen_decode`` (24 rows) and ``gen_prefill`` (a
    128- and a 512-token chunk) over the state kind's one pool and a
    one-column table, the pool updated in place, every weight read as
    stored, the counts handed back."""
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model, params, pools = _retention_cell(sds)
    fn = jax.jit(functools.partial(gp._model_step, model=model,
                                   attention_kernel="paged"),
                 donate_argnums=(1,))
    compiled = fn.lower(
        params, pools, sds((S, T), jnp.int32), sds((S, T), jnp.int32),
        sds((S,), jnp.int32), sds((S, 1), jnp.int32),
        sds((S,), jnp.uint32), sds((S,), jnp.uint32), sds((S,), jnp.float32),
        sds((S,), jnp.int32), sds((S,), jnp.float32)).compile()
    text = compiled.as_text()
    name = "_ret_call_decode" if T == 1 else f"_ret_call_t{T}_prefill"
    assert text.count(name) >= 2
    # (the kernel's first result IS its pool operand: aliased)
    makers = {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
              for ln in text.splitlines() if "= f32[2,25,8,65,136,128]" in ln}
    assert makers <= _IN_PLACE | {"get-tuple-element"}, makers
    _no_argument_copies(text)
    assert "retention_decode_rows" in model.counters
    # a 512-token chunk's temporaries: the reckoning was 1-1.5 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# -- phi-4-mini-flash: state, window and ONE full pool side by side (PR 44) --

_SBY_KINDS = ("ssm", "swa", "ssm", "full", "gmu", "cross")


def _sambay_cell(sds, kinds=_SBY_KINDS, **model_kw):
    """The published widths at a depth that has every mixer once (the
    Mamba layers twice: the second hands on its memory), with the cell's
    pools: 18,000 blocks of 32 of the full kind (a table 1,024 wide: one
    of 2,048 a row, blocks of 16, is 1 MB of scalars at 128 rows and does
    not fit the tiles body's fast scalar memory), the window kind and the
    states of 128 slots."""
    from mxnet_tpu.parallel import sambay_lm as sl

    cfg = sl.SambaYConfig(layer_kinds=kinds, num_hidden_layers=len(kinds))
    model = sl.SambaYLM(cfg, max_len=32768, **model_kw)
    params = {k: sds(s, jnp.bfloat16)
              for k, s in sl.sambay_param_shapes(cfg).items()}
    full, window, state = model.cache_spec()["kinds"]
    pools = tuple(sds((1, 18000, 32, w), jnp.bfloat16)
                  for _, w in full["pools"]) \
        + tuple(sds((window["n_layers"], 2338, 32, w), jnp.bfloat16)
                for _, w in window["pools"]) \
        + tuple(sds((state["n_layers"], 129) + s, jnp.float32)
                for _, s in state["state"])
    return model, params, pools


@pytest.mark.parametrize("B,T", [(128, 1), (1, 128), (1, 512)],
                         ids=["decode", "prefill128", "prefill512"])
def test_selective_scan_kernels_compile_at_the_cells_shapes(one_chip,
                                                            monkeypatch, B,
                                                            T):
    """``_ssm_call_decode`` (128 rows, a row's state of 16 x 5,120 float32
    a block: the first 16 of the pool's 24 sublanes) and ``_ssm_call_t<T>_prefill`` (rows x 10 tiles of 512
    channels, the chunk's positions in order) at
    ``phi-4-mini-flash``'s widths: the pool is updated in place, never
    copied."""
    from mxnet_tpu.ops import selective_scan as ss

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f32 = jnp.float32

    def fn(step, u, Bm, Cm, A, fresh, pool, slots, conv):
        old = ss.conv_state(pool, 3, slots, 4, kernel=True)
        return ss.selective_scan(step, u + old[:, :1], Bm, Cm, A, fresh, pool,
                                 slots, conv, layer=3, kernel=True)

    text = jax.jit(fn, donate_argnums=(6,)).lower(
        sds((B, T, 5120), f32), sds((B, T, 5120), f32), sds((B, T, 16), f32),
        sds((B, T, 16), f32), sds((16, 5120), f32), sds((B,), jnp.bool_),
        sds((9, 129, 24, 5120), f32), sds((B,), jnp.int32),
        sds((B, 3, 5120), f32)).compile().as_text()
    assert "_ssm_call_conv_decode" in text
    assert ("_ssm_call_decode" if T == 1 else f"_ssm_call_t{T}_prefill") \
        in text
    assert not [ln for ln in text.splitlines()
                if "f32[9,129,24,5120" in ln and " copy(" in ln]


@pytest.mark.parametrize("kind,S,T,ring", [
    ("gen_decode", 128, 1, 32), ("gen_fill", 1, 512, 64),
    ("gen_prefill", 1, 512, 64), ("gen_prefill", 1, 128, 32)],
    ids=["decode", "fill512", "final512", "final128"])
def test_sambay_step_program_compiles_at_the_cells_shapes(one_chip,
                                                          monkeypatch, kind,
                                                          S, T, ring):
    """The cell's programs as the service dispatches them, at a depth that
    has every mixer: ``gen_decode`` (128 rows), ``gen_fill`` (a chunk that
    is not a prompt's last: no head) and ``gen_prefill`` (a prompt's last
    chunk: the cross-decoder at one position) over the three kinds' pools
    and a table a kind, every pool updated in place, every weight read as
    stored — the tied embedding as the head's weight among them —, the
    counts handed back."""
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model, params, pools = _sambay_cell(sds)
    i32 = jnp.int32
    args = (params, pools, sds((S, T), i32), sds((S, T), i32), sds((S,), i32),
            (sds((S, 1024), i32), sds((S, ring), i32), sds((S, 1), i32)))
    if kind == "gen_fill":
        step = gp._fill_step
    else:
        step = gp._model_step
        args += (sds((S,), jnp.uint32), sds((S,), jnp.uint32),
                 sds((S,), jnp.float32), sds((S,), i32),
                 sds((S,), jnp.float32))
    compiled = jax.jit(functools.partial(
        step, model=model, attention_kernel="paged"),
        donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    phase = "decode" if T == 1 else "prefill"
    assert text.count("_ssm_call_decode" if T == 1
                      else f"_ssm_call_t{T}_prefill") >= 2
    assert f"_t{T}_swa_{phase}" in text
    # a chunk that fills runs no attention of the full kind and no head
    assert (f"_t1_cross_{phase}" in text) == (kind != "gen_fill")
    assert (f"_t1_full_{phase}" in text) == (kind != "gen_fill")
    for shape in ("bf16[1,18000,32,1280]", "bf16[1,2338,32,1280]",
                  "f32[2,129,24,5120]"):
        makers = {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
                  for ln in text.splitlines() if f"= {shape}" in ln}
        assert makers <= _IN_PLACE | {"get-tuple-element"}, (shape, makers)
    _no_argument_copies(text)
    assert "ssm_decode_rows" in model.counters
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# -- granite-4.0-h-micro: a matrix state larger than the paged K and V (PR 51)

def _granite_cell(sds, n_blocks=8192, slots=64):
    """The published widths at FULL depth (40 layers: the memory read
    below is the cell's) with the cell's pools: 8,192 blocks of 32 of the
    four attention layers' K and V (a table 1,024 wide) and the states of
    64 slots and the scratch, 5.21 GB."""
    from mxnet_tpu.parallel import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig()
    model = gh.GraniteHybridLM(cfg, max_len=32768)
    params = {k: sds(s, jnp.bfloat16)
              for k, s in gh.granite_hybrid_param_shapes(cfg).items()}
    full, state = model.cache_spec()["kinds"]
    pools = tuple(sds((full["n_layers"], n_blocks, 32, w), jnp.bfloat16)
                  for _, w in full["pools"]) \
        + tuple(sds((state["n_layers"], slots + 1) + s, jnp.float32)
                for _, s in state["state"])
    return model, params, pools


@pytest.mark.parametrize("B,T", [(64, 1), (1, 128), (1, 1024)],
                         ids=["decode", "prefill128", "prefill1024"])
def test_ssd_kernels_compile_at_the_cells_shapes(one_chip, monkeypatch, B, T):
    """``_ssd_call_decode`` (64 rows, a row's state of 136 x 4,096 float32 a
    block) and ``_ssd_call_t<T>_prefill`` (rows x 8 lane tiles of 512 x
    sub-chunks of 128 positions, a head's column of the running decay
    spread over lanes inside the kernel) at ``granite-4.0-h-micro``'s
    widths: the pool is updated in place, never copied."""
    from mxnet_tpu.ops import ssd

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f32 = jnp.float32
    H, di, N = 64, 4096, 128

    def fn(step, x, Bm, Cm, A, fresh, pool, slots, conv):
        old = ssd.conv_state(pool, 3, slots, 4, 2 * N, kernel=True)
        return ssd.ssd(step, x + old[:, :1, :di], Bm, Cm, A, fresh, pool,
                       slots, conv, layer=3, kernel=True)

    text = jax.jit(fn, donate_argnums=(6,)).lower(
        sds((B, T, H), f32), sds((B, T, di), f32), sds((B, T, N), f32),
        sds((B, T, N), f32), sds((H,), f32), sds((B,), jnp.bool_),
        sds((36, 65, N + 8, di), f32), sds((B,), jnp.int32),
        sds((B, 3, di + 2 * N), f32)).compile().as_text()
    assert "_ssd_call_conv_decode" in text
    assert ("_ssd_call_decode" if T == 1 else f"_ssd_call_t{T}_prefill") \
        in text
    assert not [ln for ln in text.splitlines()
                if "f32[36,65,136,4096" in ln and " copy(" in ln]


@pytest.mark.parametrize("kind,S,T", [
    ("gen_decode", 64, 1), ("gen_prefill", 1, 1024)],
    ids=["decode", "final1024"])
def test_granite_step_program_compiles_at_the_cells_shapes(one_chip,
                                                           monkeypatch, kind,
                                                           S, T):
    """The cell's decode program (64 rows) and its longest prefill program
    (a prompt's last chunk of 1,024 positions: every layer, the head at one
    position) as the service dispatches them, at the published widths and
    depth over the cell's pools: both kinds' pools updated in place, every
    weight read as stored — the tied embedding as the head's weight among
    them —, XLA never indexing the 5.2 GB state pool itself, and the peak
    of device memory under the chip's 15.75 GB."""
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model, params, pools = _granite_cell(sds)
    i32 = jnp.int32
    args = (params, pools, sds((S, T), i32), sds((S, T), i32), sds((S,), i32),
            (sds((S, 1024), i32), sds((S, 1), i32)), sds((S,), jnp.uint32),
            sds((S,), jnp.uint32), sds((S,), jnp.float32), sds((S,), i32),
            sds((S,), jnp.float32))
    compiled = jax.jit(functools.partial(
        gp._model_step, model=model, attention_kernel="paged"),
        donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    phase = "decode" if T == 1 else "prefill"
    assert text.count("_ssd_call_decode" if T == 1
                      else f"_ssd_call_t{T}_prefill") >= 36
    assert text.count(f"_ssd_call_conv_{phase}") >= 36
    assert f"_t{T}_full_{phase}" in text
    for shape in ("bf16[4,8192,32,512]", "f32[36,65,136,4096]"):
        makers = {re.search(r"\} ([a-z\-]+)\(", ln).group(1)
                  for ln in text.splitlines() if f"= {shape}" in ln}
        assert makers <= _IN_PLACE | {"get-tuple-element"}, \
            (shape, makers)
    _no_argument_copies(text)
    assert "ssd_decode_rows" in model.counters
    mem = compiled.memory_analysis()
    # arguments (weights 6.38 GB, K and V 2.15, states 5.21) and what the
    # program needs besides them; the donated pools come back in place
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 13.7e9 < peak < 15.75e9, peak
    assert mem.temp_size_in_bytes < 1.0e9
