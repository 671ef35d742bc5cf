"""A tiny configuration of the block-diffusion cell for the CPU
rehearsals: the cell's own files with the sizes cut (never used on the
chip).  ``tiny.py`` holds the other drivers' and ``drive``."""
import time

from perfbench import harness
from perfbench.tests.tiny import CPU_HOOKS


def block_diffusion_context(seed=7, seconds=2.0, trace=False, n_layers=2,
                            **hooks):
    cfg = harness.load_json("configs", "sdar-30b-a3b.json")
    cfg.update(num_hidden_layers=n_layers, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, vocab_size=97, vocab=97,
               max_position_embeddings=128, max_len=128, mask_token_id=96,
               decode_kernel="gather",
               service={"max_slots": 4, "block_size": 8, "num_blocks": 96,
                        "seq_buckets": [16, 64]})
    t = harness.load_json("traffic", "blockdiff-sat.json")
    t.update(prompt={"median": 24, "sigma": 0.7, "min": 5, "max": 64},
             output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             ramp_seconds=0.5, trace_seconds=0.5, clients=8, rounds=128)
    return harness.Context(
        {"name": "sdar-30b-a3b-blockdiff-sat", "chips": 1}, cfg, t, seed,
        seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, "ref_pads": (128,), **hooks})
