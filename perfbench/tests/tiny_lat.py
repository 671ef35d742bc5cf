"""A tiny configuration of the latent-decode cell for the CPU rehearsals:
the cell's own files with the sizes cut (never used on the chip).
``tiny.py`` holds the other drivers' and ``drive``."""
import time

from perfbench import harness
from perfbench.tests.tiny import CPU_HOOKS


def latent_decode_context(seed=7, seconds=2.0, trace=False, n_layers=3,
                          **hooks):
    cfg = harness.load_json("configs", "dots-vlm1.json")
    cfg.update(num_hidden_layers=n_layers, first_k_dense_replace=1,
               hidden_size=64, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               n_routed_experts=4, experts_held=[4, 8], n_group=4,
               topk_group=2, num_experts_per_tok=4, vocab_size=97, vocab=97,
               max_position_embeddings=128, max_len=128,
               published=dict(cfg["published"], n_routed_experts=16),
               rope_scaling=dict(cfg["rope_scaling"],
                                 original_max_position_embeddings=64),
               # the CPU multiplies no bfloat16 pair into float32 in a
               # batched product (the absorbed form's), so float32 here
               param_dtype="float32", decode_kernel="gather",
               service={"max_slots": 4, "block_size": 8, "num_blocks": 96,
                        "seq_buckets": [16, 64]})
    t = harness.load_json("traffic", "latent-decode-sat.json")
    t.update(prompt={"median": 24, "sigma": 0.7, "min": 5, "max": 64},
             output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             ramp_seconds=0.5, trace_seconds=0.5, clients=8, rounds=128)
    return harness.Context(
        {"name": "dots-vlm1-latent-decode-sat", "chips": 1}, cfg, t, seed,
        seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, "ref_pads": (128,), **hooks})
