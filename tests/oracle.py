"""The greedy reference of the generation tests (docs/testing.md): the plain
full forward pass of a causal model over the whole sequence, one new token a
call.  No cache, no batching, no kernel and nothing of ``mxnet_tpu.serving``.

The pass is jitted ONCE a model, at the configuration's ``max_len``: the
sequence is padded on the right and the logits are read at its last real
position, which the padding behind it cannot reach.  A loop over growing
lengths compiles every operation of the model anew for every length.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import transformer as tr

# the GPT-2 block the generation, speculative, preemption, paged-attention
# and router files all serve
CFG = tr.TransformerConfig(vocab=40, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64)


@pytest.fixture(scope="module")
def params():
    """Seeded weights of ``CFG``, once a module that imports the fixture."""
    return tr.transformer_lm_init(CFG, jax.random.PRNGKey(0))


def greedy(last_logits, prompt, n_new):
    """``n_new`` greedy tokens behind ``prompt``; ``last_logits(seq)`` is the
    model's logits at the last position of the token list ``seq``."""
    seq = [int(t) for t in prompt]
    for _ in range(n_new):
        seq.append(int(np.argmax(last_logits(seq))))
    return seq[len(prompt):]


@functools.lru_cache(maxsize=None)
def _padded_apply(cfg):
    positions = jnp.arange(cfg.max_len, dtype=jnp.int32)
    return jax.jit(lambda params, tokens: tr.transformer_lm_apply(
        params, tokens[None, :], positions, cfg)[0])


def greedy_oracle(params, prompt, n_new, cfg=CFG):
    """Greedy decoding through ``transformer_lm_apply`` in the dtype of
    ``params`` (cast them for a bf16 oracle)."""
    apply = _padded_apply(cfg)

    def last_logits(seq):
        tokens = np.zeros(cfg.max_len, np.int32)
        tokens[:len(seq)] = seq
        return np.asarray(apply(params, tokens)[len(seq) - 1])

    with pytest.MonkeyPatch.context() as mp:
        # a test that forces the kernel layer on must not put its fused
        # LayerNorm into the reference (the gate is read when tracing)
        mp.setenv("TPUMX_PALLAS", "0")
        return greedy(last_logits, prompt, n_new)
