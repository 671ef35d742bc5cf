"""A tiny configuration of the ``afmoe`` cell for the CPU rehearsals: the
cell's own files with the sizes cut (never used on the chip).  ``tiny.py``
holds the other drivers' and ``drive``."""
import time

from perfbench import harness
from perfbench.tests.tiny import CPU_HOOKS

S, F = "sliding_attention", "full_attention"


def afmoe_decode_context(seed=7, seconds=2.0, trace=False, **hooks):
    cfg = harness.load_json("configs", "trinity-mini.json")
    cfg.update(num_hidden_layers=4, layer_types=[S, S, S, F],
               num_dense_layers=2, hidden_size=64, num_attention_heads=8,
               num_key_value_heads=2, head_dim=16, sliding_window=16,
               intermediate_size=96, moe_intermediate_size=32, num_experts=4,
               experts_held=[4, 8], num_experts_per_tok=4, vocab_size=97,
               vocab=97, max_position_embeddings=128, max_len=128,
               published=dict(cfg["published"], num_experts=16),
               param_dtype="float32", decode_kernel="gather",
               service={"max_slots": 4, "block_size": 4, "num_blocks": 160,
                        "seq_buckets": [16, 64]})
    t = harness.load_json("traffic", "mixedlen-decode-sat.json")
    t.update(prompt={"median": 20, "sigma": 0.9, "min": 5, "max": 64},
             output={"median": 14, "sigma": 0.5, "min": 4, "max": 28},
             ramp_seconds=0.5, trace_seconds=0.5, clients=8, rounds=128)
    return harness.Context(
        {"name": "trinity-mini-mixedlen-decode-sat", "chips": 1}, cfg, t,
        seed, seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, "ref_pads": (128,), **hooks})
