"""Device scopes (docs/observability.md "Device scopes"): the program's own
``jax.named_scope`` names arrive in the compiled HLO of the fused fit step
and of every serving program, change nothing in the lowered program, and
``observability.device_scopes`` maps a trace's device events back to them.
All on the CPU; what the chip's trace looks like is PERF.md's.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.observability import device_scopes as ds


def _net(filters=4):
    data = mx.sym.Variable("data")
    x = mx.sym.Convolution(data, num_filter=filters, kernel=(3, 3),
                           pad=(1, 1), name="conv1")
    x = mx.sym.BatchNorm(x, name="bn1")
    x = mx.sym.Activation(x, act_type="relu", name="relu1")
    x = mx.sym.Flatten(x)
    x = mx.sym.FullyConnected(x, num_hidden=10, name="fc1")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _fit(n_ctx, filters=4, image=8, batch=8, steps=4, callback=None):
    rng = np.random.default_rng(0)
    X = rng.random((batch * steps, 3, image, image), dtype=np.float32)
    Y = rng.integers(0, 10, batch * steps).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch, label_name="softmax_label")
    mod = mx.mod.Module(_net(filters),
                        context=[mx.cpu(i) for i in range(n_ctx)])
    mod.fit(it, num_epoch=1, optimizer="sgd",
            kvstore=mx.kv.create("tpu_sync"),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            batch_end_callback=callback)
    assert mod._fused_step_count == steps
    return mod


@pytest.fixture(scope="module")
def fit_texts():
    """Compiled HLO of the tiny fused step on one device and under the
    8-device dp mesh."""
    return {n: _fit(n)._exec.fused_step_hlo() for n in (1, 8)}


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("devices,want", [
    (1, "jvp(Convolution)/conv1/conv_general_dilated"),
    (1, "transpose(jvp(Convolution))/conv1/conv_general_dilated"),
    (1, "jvp(BatchNorm)/bn1/"),
    (1, "transpose(jvp(FullyConnected))/fc1/dot_general"),
    (1, "/optimizer.update/"),
    (8, "/kvstore.allreduce/psum"),
    (8, "shard_map/jvp(Convolution)/conv1/conv_general_dilated"),
    (8, "/optimizer.update/"),
])
def test_fused_step_names_its_operations(fit_texts, devices, want):
    assert any(want in n for n in _op_names(fit_texts[devices]))


def test_the_allreduce_instruction_carries_the_kvstore_scope(fit_texts):
    lines = [ln for ln in fit_texts[8].splitlines()
             if re.search(r"= .* all-reduce(-start)?\(", ln)]
    assert lines
    assert all("kvstore.allreduce" in ln or "telemetry" in ln
               for ln in lines)
    t = ds.ProgramTable("fused_step", fit_texts[8])
    name = re.match(r"\s*%?([\w.\-]+) = ", lines[0]).group(1)
    assert t.instrs[name][1] == ds.Resolved("fused_step",
                                            "kvstore.allreduce", "forward")


# -- the serving programs -----------------------------------------------------

def _service(model, **gc):
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    key = jax.random.PRNGKey(0)
    kw = dict(max_slots=2, block_size=8, num_blocks=32, seq_buckets=[16],
              prefix_cache=True)
    if model == "gpt2":
        from mxnet_tpu.parallel.transformer import (TransformerConfig,
                                                    transformer_lm_init)
        m = TransformerConfig(vocab=97, d_model=32, n_heads=2, n_layers=2,
                              d_ff=64, max_len=64)
        params = transformer_lm_init(m, key)
    elif model == "sdar":
        from mxnet_tpu.parallel import sdar_moe as sm
        cfg = sm.SdarMoeConfig(
            vocab_size=97, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, mask_token_id=96)
        m = sm.SdarMoeLM(cfg, max_len=64, kv_dtype=jnp.float32)
        params = sm.sdar_moe_init(cfg, key)
    elif model == "dots":
        from mxnet_tpu.parallel import latent_moe as lm
        cfg = lm.LatentMoeConfig(
            vocab_size=97, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_hidden_layers=2,
            first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=12,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, n_routed_experts=8, num_experts_per_tok=2,
            n_group=2, topk_group=1, max_position_embeddings=64,
            rope_original_max_position_embeddings=32)
        m = lm.LatentMoeLM(cfg, max_len=64, kv_dtype=jnp.float32,
                           longest_chunk=16)
        params = lm.latent_moe_init(cfg, key)
    else:
        from mxnet_tpu.parallel import hybrid_moe as hm
        cfg = hm.HybridMoeConfig(
            hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1),
            num_hidden_layers=2, hidden_size=32, num_attention_heads=4,
            num_key_value_heads=1, swa_num_attention_heads=4,
            swa_num_key_value_heads=2, head_dim=12, swa_head_dim=12,
            v_head_dim=8, swa_v_head_dim=8, sliding_window=8,
            partial_rotary_factor=0.334, intermediate_size=48,
            moe_intermediate_size=16, n_routed_experts=8,
            num_experts_per_tok=2, n_group=1, topk_group=1, vocab_size=97,
            max_position_embeddings=64)
        # a chip's share of the experts: the expert layer is a loop over
        # the held rows' tiles (``sdar_moe.expert_products``), whose body
        # keeps the vocabulary (``while/body`` is jax's, not a scope)
        m = hm.HybridMoeLM(cfg, max_len=64, kv_dtype=jnp.float32,
                           longest_chunk=16, experts_held=(2, 4))
        params = hm.hybrid_moe_init(cfg, key, experts_held=(2, 4))
        kw.update(prefix_cache=None)
    kw.update(gc)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPUMX_PALLAS", "0")
        svc = GenerationService(params, m, GenerationConfig(**kw),
                                start=False)
    svc.warmup()
    return svc


@pytest.fixture(scope="module")
def services():
    """One warmed service a model (and GPT-2's speculative one), made
    when first asked for."""
    made = {}

    def get(name):
        if name not in made:
            model, _, variant = name.partition("-")
            made[name] = _service(model, **{
                "": {}, "verify": dict(speculative=True, draft_k=2)}[variant])
        return made[name]

    yield get
    for svc in made.values():
        svc.stop(drain=False, timeout=30)


def _tables(svc):
    """``[(kind, ProgramTable)]`` of a service's programs; the kind is
    what each text's outermost scope says."""
    tables = [ds.ProgramTable(kind, thunk())
              for kind, _, _, thunk in svc._programs.device_programs()]
    return [(t.kind, t) for t in tables]


def _scopes(tables, kind):
    return {re.sub(r"^layer\d+/", "layer/", r.scope)
            for k, t in tables if k == kind
            for _, r in t.instrs.values() if r is not None}


PARTS = {"gpt2": {"embed", "layer/norm", "layer/attn.proj",
                  "layer/attn.cache_write", "layer/attn.kernel", "layer/ffn",
                  "head", "sample"},
         "sdar": {"embed", "layer/norm", "layer/attn.proj",
                  "layer/attn.cache_write", "layer/attn.kernel",
                  "layer/moe.route", "layer/moe.experts",
                  "layer/moe.combine", "head", "sample"},
         "dots": {"embed", "layer/norm", "layer/attn.proj",
                  "layer/attn.cache_write", "layer/attn.kernel", "layer/ffn",
                  "layer/moe.route", "layer/moe.experts",
                  "layer/moe.combine", "head", "sample"},
         "mimo": {"embed", "layer/norm", "layer/attn.proj",
                  "layer/attn.cache_write", "layer/attn.kernel", "layer/ffn",
                  "layer/moe.route", "layer/moe.experts",
                  "layer/moe.combine", "head", "sample"}}


@pytest.mark.parametrize("model", sorted(PARTS))
def test_model_step_names_its_layer_parts(services, model):
    tables = _tables(services(model))
    kind = "block" if model == "sdar" else "decode"
    got = _scopes(tables, kind)
    assert PARTS[model] <= got, PARTS[model] - got
    layers = {r.scope.split("/")[0] for k, t in tables if k == kind
              for _, r in t.instrs.values()
              if r is not None and r.scope.startswith("layer")}
    assert layers == {"layer0", "layer1"}


@pytest.mark.parametrize("kind,service", [
    ("decode", "gpt2"), ("prefill", "gpt2"), ("carry", "gpt2"),
    ("first_token", "gpt2"), ("block_copy", "gpt2"), ("verify", "gpt2-verify"), ("block", "sdar"),
    ("fill", "sdar"), ("carry", "sdar")])
def test_every_program_kind_says_its_kind(services, kind, service):
    """The outermost scope of each traced function is the program's kind
    as the engine counts it, and the label the resolver gives it."""
    texts = [thunk() for _, _, _, thunk
             in services(service)._programs.device_programs()]
    mine = [t for t in texts if ds.ProgramTable(None, t).kind == kind]
    assert mine
    for text in mine:
        names = {n for n in _op_names(text) if n.startswith("jit(")}
        assert names
        assert all(n.split("/")[1] == kind for n in names), names


# -- nothing moves ------------------------------------------------------------

def _lowered(jitted, avals, **as_text):
    """The lowered text of a FRESH trace of what ``jitted`` wraps (jax
    would hand a second lowering of ``jitted`` itself its cached trace)."""
    inner = jitted.__wrapped__
    return jax.jit(lambda *a: inner(*a)).lower(*avals).as_text(**as_text)


def _without_scopes(mp):
    mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())


@pytest.mark.parametrize("devices", [1, 8])
def test_fused_step_lowers_to_the_same_text_without_scopes(devices):
    thunk = _fit(devices, steps=1)._exec._fused_probe[1]
    jitted, avals = thunk.args
    witness = lambda: "optimizer.update" in _lowered(  # noqa: E731
        jitted, avals, debug_info=True)
    scoped = _lowered(jitted, avals)
    assert witness()        # the names are in the locations ...
    # ... and the compiled text's are this build's
    assert not ds.stale(jitted.lower(*avals), thunk())
    with pytest.MonkeyPatch.context() as mp:
        _without_scopes(mp)
        plain = _lowered(jitted, avals)
        assert not witness()
    assert scoped == plain


@pytest.mark.parametrize("service", ["gpt2", "sdar", "dots", "mimo"])
def test_serving_programs_lower_to_the_same_text_without_scopes(services,
                                                                service):
    programs = services(service)._programs.device_programs()
    assert programs
    for kind, _, _, thunk in programs:
        jitted, avals = thunk.args
        assert not ds.stale(jitted.lower(*avals), thunk()), kind
        scoped = _lowered(jitted, avals)
        with pytest.MonkeyPatch.context() as mp:
            _without_scopes(mp)
            assert _lowered(jitted, avals) == scoped, kind


# -- the resolver -------------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(fused)/jvp(Convolution)/conv1/conv_general_dilated",
     ("Convolution/conv1", "forward")),
    ("jit(fused_spmd)/shard_map/transpose(jvp(BatchNorm))/bn1/reduce_sum",
     ("BatchNorm/bn1", "backward")),
    ("jit(fused)/transpose(jvp(SoftmaxOutput))/softmax/jit(_one_hot)/eq",
     ("SoftmaxOutput/softmax", "backward")),
    ("jit(_unknown)/decode/layer3/moe.combine/scatter-add",
     ("decode/layer3/moe.combine", "forward")),
    ("jit(f)/decode/layer1/moe.experts/while/body/mk,kn->mn/dot_general",
     ("decode/layer1/moe.experts", "forward")),
    ("jit(f)/decode/sample/cond/branch_2_fun/sort", ("decode/sample",
                                                     "forward")),
    ("jit(f)/decode/layer0/attn.kernel/broadcast_in_dim;jit(f)/decode/"
     "layer0/attn.kernel/tile", ("decode/layer0/attn.kernel", "forward")),
    ("reduce_sum", ("", "forward")),
])
def test_parse_op_name(op_name, want):
    assert ds.parse_op_name(op_name) == want


TPU_EVENT = ("%fusion.12 = (bf16[256,64]{1,0:T(8,128)(2,1)S(1)}, f32[64]{0})"
             " fusion(bf16[256,64]{1,0:T(8,128)(2,1)} %copy-done, f32[4]{0} "
             "%p.1), kind=kOutput, calls=%fused_computation.3")

HLO = """HloModule jit_{mod}, is_scheduled=true

%fused_computation.3 (a: f32[4]) -> f32[4] {{
  %a = f32[4]{{0}} parameter(0)
  ROOT %m = f32[4]{{0}} multiply(%a, %a), metadata={{op_name="jit({mod})/{kind}/{inner}/mul"}}
}}

ENTRY %main (p.1: f32[4]) -> f32[4] {{
  %p.1 = f32[4]{{0}} parameter(0)
  %copy-start = (bf16[256,64]{{1,0}}, bf16[256,64]{{1,0}}, u32[]) copy-start(%p.1)
  %copy-done = bf16[256,64]{{1,0}} copy-done(%copy-start)
  %fusion.12 = (bf16[256,64]{{1,0}}, f32[64]{{0}}) fusion(%copy-done, %p.1), kind=kOutput, calls=%fused_computation.3{meta}
  ROOT %only.{kind} = f32[4]{{0}} negate(%p.1), metadata={{op_name="jit({mod})/{kind}/head/neg"}}
}}
"""


def _table(*programs):
    return ds.Table([ds.ProgramTable(kind, HLO.format(
        mod="step", kind=kind, inner=inner,
        meta=f', metadata={{op_name="jit(step)/{kind}/{scope}/dot"}}'
        if scope else "")) for kind, scope, inner in programs])


def test_a_tpu_events_whole_text_resolves_by_name_and_result_shapes():
    t = _table(("decode", "layer0/ffn", "layer0/ffn"))
    assert t.resolve(TPU_EVENT) == ("decode", "layer0/ffn", "forward")
    assert t.resolve("fusion.12") == ("decode", "layer0/ffn", "forward")
    assert t.resolve(TPU_EVENT.replace("256,64]{1,0:T(8,128)(2,1)S(1)}",
                                       "128,64]{1,0}")) is None
    assert t.resolve("%fusion.99 = f32[] fusion()") is None


def test_a_fusion_takes_its_bodys_scope_and_a_copy_its_waiters():
    # the fusion's own op_name is its root's alone: its body says more
    t = _table(("decode", "layer0/ffn", "layer1/norm"))
    assert t.resolve("fusion.12").scope == "layer1/norm"
    # the copy the compiler made is waited for by the fusion
    assert t.resolve("copy-done").scope == "layer1/norm"
    assert t.resolve("copy-start").scope == "layer1/norm"
    # a fusion around a product is the product's, whatever else is in it
    text = HLO.format(mod="step", kind="decode", inner="layer1/norm",
                      meta="").replace(
        "  ROOT %m = ", "  %d = f32[4]{0} dot(%a, %a), metadata={op_name="
        '"jit(step)/decode/layer0/attn.proj/dot_general"}\n  %n = f32[4]{0} '
        'negate(%a), metadata={op_name="jit(step)/decode/layer1/norm/neg"}'
        "\n  ROOT %m = ")
    assert ds.ProgramTable(None, text).instrs["fusion.12"][1].scope == \
        "layer0/attn.proj"
    # an argument's name is no scope: its copy goes to whoever waits
    text = HLO.format(mod="step", kind="decode", inner="layer1/norm",
                      meta="").replace(
        "copy-start(%p.1)", 'copy-start(%p.1), metadata={op_name="p"}')
    assert ds.ProgramTable(None, text).instrs["copy-start"][1].scope == \
        "layer1/norm"


def test_a_name_two_programs_scope_differently_resolves_to_nothing():
    t = _table(("decode", "layer0/ffn", "layer0/ffn"),
               ("prefill", "layer1/norm", "layer1/norm"))
    assert t.resolve("fusion.12") is None and t.resolve(TPU_EVENT) is None
    assert t.resolve("only.decode") == ("decode", "head", "forward")
    # ... but the run it lies in says which program it belongs to
    got = t.resolve_stream(["fusion.12", "only.decode", "nobody's",
                            "fusion.12", "only.prefill", "fusion.12"])
    assert got == [("decode", "layer0/ffn", "forward"),
                   ("decode", "head", "forward"), None,
                   ("prefill", "layer1/norm", "forward"),
                   ("prefill", "head", "forward"), None]
    same = _table(("decode", "layer0/ffn", "layer0/ffn"),
                  ("decode", "layer0/ffn", "layer0/ffn"))
    assert same.resolve("fusion.12") == ("decode", "layer0/ffn", "forward")


STALE = """
import sys, jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from mxnet_tpu.observability import device_scopes as ds
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.tanh(x @ x) + 1
j, x = jax.jit(f), jnp.ones((64, 64))
j(x)
text = ds.text_thunk(j, (x,))()
print("RESULT", sys.argv[1] in text, ds.build_stats()["stale"])
"""


def test_a_cache_entry_with_another_builds_scopes_is_compiled_again(
        tmp_path):
    """jax's persistent cache leaves metadata out of its key: the same
    program under a renamed scope hits the older build's entry, whose
    text says the older name.  The resolver notices and compiles again."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    got = []
    for name in ("old.name", "new.name", "new.name"):
        run = subprocess.run([sys.executable, "-c", STALE, name,
                              str(tmp_path)], env=env, cwd=root,
                             capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-3000:]
        got.append(run.stdout.strip().splitlines()[-1])
    assert got == ["RESULT True 0", "RESULT True 1", "RESULT True 1"]


def test_self_times_take_nested_events_out_of_their_parent():
    evs = [(0, 100, "while"), (10, 30, "a"), (30, 60, "b"), (100, 120, "c"),
           (40, 50, "b.inner")]
    own = {e[2]: t for e, t in ds.self_times(evs)}
    assert own == {"while": 50, "a": 20, "b": 20, "b.inner": 10, "c": 20}
    assert sum(own.values()) == 120


@pytest.fixture
def owned_trace(tmp_path, monkeypatch):
    """``mx.profiler`` owns a jax trace for the test."""
    monkeypatch.setenv("TPUMX_JAX_TRACE_DIR", str(tmp_path))
    ds.reset()
    yield str(tmp_path)
    if mx.profiler._state["running"]:
        mx.profiler.set_state("stop")
    mx.profiler._state["device_trace"] = None
    ds.reset()


def test_resolver_names_a_traced_fit_and_dumps_shows_it(owned_trace):
    def on_batch(param):
        if param.nbatch == 1:
            mx.profiler.set_state("run")

    mod = _fit(1, filters=16, image=32, batch=16, steps=8, callback=on_batch)
    mx.profiler.set_state("stop")
    del mod                 # the session outlives the programs' owner
    built = ds.build_stats()["programs"]
    got = ds.device_table(ds.find_xplane(owned_trace))
    assert ds.build_stats()["programs"] == built + 1
    assert got["resolved_ms"] >= 0.9 * got["busy_ms"] > 0
    assert set(got["by_kind"]) <= {"fused_step", ds.UNSCOPED}
    rows = ds.rollup(got["by_scope"])
    for scope, direction in (("Convolution/conv1", "forward"),
                             ("Convolution/conv1", "backward"),
                             ("BatchNorm/bn1", "backward"),
                             ("optimizer.update", "forward")):
        assert rows[("fused_step", scope, direction)] > 0
    text = mx.profiler.dumps()
    assert "Device time by program kind" in text
    assert "fused_step / Convolution/conv1 (backward)" in text
    assert ds.build_stats()["programs"] == built + 1    # parsed once


def test_resolver_names_a_traced_generation_run(services, owned_trace):
    svc = services("gpt2")
    svc.start()
    mx.profiler.set_state("run")
    out = svc.generate([1, 2, 3, 4, 5], max_new_tokens=8)
    mx.profiler.set_state("stop")
    assert len(out) == 8
    got = ds.device_table(ds.find_xplane(owned_trace))
    assert got["resolved_ms"] >= 0.9 * got["busy_ms"] > 0
    assert {"decode", "prefill"} <= set(got["by_kind"])
    rows = ds.rollup(got["by_scope"])
    assert rows[("decode", "attn.cache_write", "forward")] > 0
    assert rows[("prefill", "head", "forward")] > 0
    # only the programs that ran in the session were compiled for it
    kinds = [p.kind for p in ds.table().programs]
    assert "block_copy" not in kinds and "decode" in kinds


def test_dumps_has_no_device_section_without_an_owned_trace():
    assert "Device time" not in mx.profiler.dumps()
