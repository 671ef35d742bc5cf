"""A tiny configuration of the hybrid-decode cell for the CPU rehearsals:
the cell's own files with the sizes cut (never used on the chip).
``tiny.py`` holds the other drivers' and ``drive``."""
import time

from perfbench import harness
from perfbench.tests.tiny import CPU_HOOKS


def hybrid_decode_context(seed=7, seconds=2.0, trace=False, n_layers=3,
                          **hooks):
    cfg = harness.load_json("configs", "mimo-v2.5.json")
    cfg.update(num_hidden_layers=n_layers, hidden_size=64,
               num_attention_heads=8, num_key_value_heads=1,
               swa_num_attention_heads=8, swa_num_key_value_heads=2,
               head_dim=24, swa_head_dim=24, v_head_dim=16,
               swa_v_head_dim=16, sliding_window=8, intermediate_size=96,
               moe_intermediate_size=32, n_routed_experts=4,
               experts_held=[4, 8], num_experts_per_tok=4, vocab_size=97,
               vocab=97, max_position_embeddings=128, max_len=128,
               published=dict(cfg["published"], n_routed_experts=16),
               param_dtype="float32", decode_kernel="gather",
               service={"max_slots": 4, "block_size": 4, "num_blocks": 160,
                        "seq_buckets": [16, 64]})
    t = harness.load_json("traffic", "hybrid-decode-sat.json")
    t.update(prompt={"median": 24, "sigma": 0.7, "min": 5, "max": 64},
             output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             ramp_seconds=0.5, trace_seconds=0.5, clients=8, rounds=128)
    return harness.Context(
        {"name": "mimo-v2.5-hybrid-decode-sat", "chips": 1}, cfg, t, seed,
        seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, "ref_pads": (128,), **hooks})
