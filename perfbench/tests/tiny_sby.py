"""A tiny configuration of the reason-decode cell for the CPU rehearsals:
the cell's own files with the sizes cut (never used on the chip).
``tiny.py`` holds the other drivers' and ``drive``."""
import time

from perfbench import harness
from perfbench.tests.tiny import CPU_HOOKS

KINDS = ["ssm", "swa", "ssm", "swa", "ssm", "full", "gmu", "cross", "gmu",
         "cross"]


def reason_decode_context(seed=7, seconds=2.0, trace=False, **hooks):
    cfg = harness.load_json("configs", "phi-4-mini-flash.json")
    cfg.update(num_hidden_layers=len(KINDS), layer_kinds=KINDS,
               hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
               intermediate_size=128, sliding_window=8, vocab_size=97,
               vocab=97, max_position_embeddings=256, max_len=256,
               assumed_values=dict(cfg["assumed_values"], dt_rank=4),
               param_dtype="float32", decode_kernel="gather",
               service={"max_slots": 4, "block_size": 4, "num_blocks": 200,
                        "seq_buckets": [16, 64, 128]})
    t = harness.load_json("traffic", "reason-decode-sat.json")
    t.update(prompt={"median": 24, "sigma": 0.7, "min": 5, "max": 128},
             output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
             ramp_seconds=0.5, trace_seconds=0.5, clients=8, rounds=128)
    return harness.Context(
        {"name": "phi-4-mini-flash-reason-decode-sat", "chips": 1}, cfg, t,
        seed, seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, "ref_pad": 256, **hooks})
