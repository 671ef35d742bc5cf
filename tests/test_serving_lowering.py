"""The serving programs of the three models whose cache is of one kind
lower to the text they lowered to before cache kinds (PR 32): GPT-2's block
(float and int8 pool), the SDAR-MoE block and the latent-attention block,
each with ``TPUMX_PALLAS`` 0 and 1, at one small shape.

What is compared is a digest of every program a warm-up compiles
(``jitted.lower(*args).as_text()`` of the programs ``programs.py`` jits, in
order, ``jax.result_info`` masked).  The digests below were taken on the
parent commit of PR 32 by this file's ``python tests/test_serving_lowering.py``
(it prints the table); a PR that means to change a program takes them anew
the same way and says so.  PR 39 did for ``sdar`` (the block step hands its
logits back position-major) and ``dots`` (``wqb``'s product stays flat behind
a barrier); GPT-2's four are still PR 32's parent's.
"""
import hashlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

PARENT = {
    "gpt2-float-0": "7b2e4313c5b1742c",
    "gpt2-float-1": "fdc5b4aff8672a10",
    "gpt2-int8-0": "dddd2d1e288981fe",
    "gpt2-int8-1": "bc556195e75456fb",
    "sdar-float-0": "3bd262c7f87cf8d9",
    "sdar-float-1": "98c83ac3f4842770",
    "dots-float-0": "42442f97a3a4aefe",
    "dots-float-1": "76b1de1ba54e5b13",
}


def _service(model, kv):
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    key = jax.random.PRNGKey(0)
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
    else:
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
    return GenerationService(
        params, m, GenerationConfig(
            max_slots=2, block_size=8, num_blocks=16, seq_buckets=[16],
            kv_dtype="int8" if kv == "int8" else None, prefix_cache=True),
        start=False)


def lowered_digest(model, kv, pallas, monkeypatch):
    """sha256 over the texts of every program the warm-up of a small
    service compiles."""
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS", str(pallas))
    texts, real = [], jax.jit

    def recording(fn, **kw):
        jitted = real(fn, **kw)
        # (the block state's carry, PR 34, and the placing of a prefill's
        # first token, PR 45, are no model programs and were not there
        # when the digests were taken)
        if sys._getframe(1).f_code.co_filename != gp.__file__ \
                or fn in (gp._carry_block, gp._place_first):
            return jitted

        def call(*args):
            texts.append(re.sub(r'jax\.result_info = "[^"]*"', "",
                                jitted.lower(*args).as_text()))
            return jitted(*args)
        return call

    monkeypatch.setattr(jax, "jit", recording)
    svc = _service(model, kv)
    n = svc.warmup()
    monkeypatch.setattr(jax, "jit", real)
    assert n == len(texts) or svc._runs_ahead   # (+ the carry)
    return hashlib.sha256("\n".join(texts).encode()).hexdigest()[:16], \
        len(texts)


@pytest.mark.parametrize("case", sorted(PARENT))
def test_one_kind_models_lower_to_the_parents_programs(case, monkeypatch):
    model, kv, pallas = case.split("-")
    digest, n = lowered_digest(model, kv, int(pallas), monkeypatch)
    assert n >= 2
    assert digest == PARENT[case]


def test_a_model_naming_one_kind_builds_todays_cache():
    """No ``kinds`` in the spec: the pools it names under ONE allocator,
    one kind that is the cache itself, and nothing for the engine's window
    code to walk."""
    from mxnet_tpu.serving.generation.kv_cache import PagedKVCache

    class _Mp(pytest.MonkeyPatch):
        pass

    with _Mp.context() as mp:
        mp.setenv("TPUMX_PALLAS", "0")
        for model, names, n_pools in (("gpt2", ["k", "v"], 2),
                                      ("sdar", ["k", "v"], 2),
                                      ("dots", ["latent"], 1)):
            svc = _service(model, "float")
            cache = svc._cache
            assert [n for n, _ in cache.layout] == names
            assert len(cache.pools) == n_pools
            assert len(cache.kinds) == 1 and svc._windows == ()
            only = cache.kinds[0]
            assert only.allocator is cache.allocator and only.window == 0
            assert only.num_blocks == cache.num_blocks == 16
            assert cache.pools[only.span] == cache.pools
            assert {p.shape[:3] for p in cache.pools} == \
                {(2, 16, 8)}
            assert "window_blocks_freed" not in svc.stats()["counts"]
            assert list(svc.stats()["cache_kinds"]) == ["kv"]
    old = PagedKVCache(2, 2, 8, num_blocks=16, block_size=8)
    assert [p.shape for p in old.pools] == [(2, 16, 8, 16)] * 2


if __name__ == "__main__":
    mp = pytest.MonkeyPatch()
    for case in sorted(PARENT):
        model, kv, pallas = case.split("-")
        print(f'    "{case}": "{lowered_digest(model, kv, int(pallas), mp)[0]}",',
              flush=True)
    mp.undo()
