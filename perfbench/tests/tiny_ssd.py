"""A tiny configuration of the chat-decode cell for the CPU rehearsals: the
cell's own files with the sizes cut (never used on the chip).  ``tiny.py``
holds the other drivers' and ``drive``."""
import time

from perfbench import harness
from perfbench.tests.tiny import CPU_HOOKS

KINDS = ["mamba", "mamba", "attention", "mamba", "mamba", "attention"]


def ssd_decode_context(seed=7, seconds=2.0, trace=False, **hooks):
    cfg = harness.load_json("configs", "granite-4.0-h-micro.json")
    cfg.update(num_hidden_layers=len(KINDS), layer_types=KINDS,
               hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
               shared_intermediate_size=128, mamba_n_heads=4, mamba_d_head=32,
               mamba_d_state=16, attention_multiplier=0.125, vocab_size=97,
               vocab=97, max_position_embeddings=256, max_len=256,
               param_dtype="float32", decode_kernel="gather",
               service={"max_slots": 4, "block_size": 4, "num_blocks": 200,
                        "seq_buckets": [16, 32, 128]})
    t = harness.load_json("traffic", "chat-decode-sat.json")
    t.update(prompt={"median": 24, "sigma": 0.7, "min": 5, "max": 128},
             output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             ramp_seconds=0.5, trace_seconds=0.5, clients=8, rounds=128)
    return harness.Context(
        {"name": "granite-4.0-h-micro-chat-decode-sat", "chips": 1}, cfg, t,
        seed, seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, "ref_pads": (256,), **hooks})
