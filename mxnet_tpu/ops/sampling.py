"""Token-sampling ops for autoregressive generation.

The functional core (`temperature_scale` / `top_k_mask` / `top_p_mask` /
`sample_logits`) is what the generation engine traces inside its compiled
decode program: every knob is a *per-row array*, so one program serves any
mix of greedy / temperature / top-k / top-p requests sharing a decode batch
— no recompile when a request's sampling config differs from its slot
neighbours.  The registry entries expose the same math as framework ops
(scalar-attr form), with numpy-parity tests in tests/test_generation.py.

Inside that one program `sample_logits` does only what its rows ask for:
a `lax.switch` on :func:`sampler_body`, a scalar computed on the device
from the step's own ``temperature`` / ``top_k`` / ``top_p`` arrays, picks
one of three bodies (:data:`SAMPLER_BODIES`):

- ``greedy`` — no row samples: ``argmax`` of the raw logits, and nothing
  else (no sort, no noise, no softmax);
- ``draw`` — some row samples and none of those filters: temperature and
  the Gumbel-max draw, no sort;
- ``filter`` — some sampling row has a top-k or top-p filter on: ONE
  descending sort over the vocabulary, both thresholds read from it.

A row's token does not depend on the body its batch took: a greedy row is
the raw argmax in all three, and a row whose filters are off passes
through ``filter`` unchanged to the bit.

Conventions (vLLM/HF-compatible):
- ``temperature <= 0`` means greedy (argmax of the raw logits; top-k/top-p
  are ignored, matching the usual serving API contract);
- ``top_k <= 0`` or ``top_k >= vocab`` disables top-k; ties at the k-th
  logit are all kept (the mask is a value threshold, not a rank cut);
- ``top_p >= 1`` disables nucleus filtering, exactly: such a row keeps
  every logit, however its softmax rounds; otherwise the kept set is the
  smallest prefix of the probability-sorted vocab whose mass reaches
  ``top_p`` (the first token is always kept, so ``top_p <= 0`` degenerates
  to top-1);
- sampling is Gumbel-max over the filtered, temperature-scaled logits —
  exactly categorical sampling, expressed as one argmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

__all__ = ["temperature_scale", "top_k_mask", "top_p_mask",
           "top_k_top_p_mask", "sample_logits", "sampler_body",
           "SAMPLER_BODIES", "speculative_verify", "fold_keys",
           "block_unmask", "NEG_INF"]

#: same finite -inf stand-in the attention masks use (exp() underflows to
#: exactly 0.0 in f32, and finite values keep XLA's max/where paths simple)
NEG_INF = -1e30


def temperature_scale(logits, temperature):
    """``logits / temperature`` with per-row (or scalar) temperature;
    rows with ``temperature <= 0`` pass through unscaled (the greedy
    branch selects on raw logits anyway)."""
    logits = jnp.asarray(logits, jnp.float32)
    t = jnp.asarray(temperature, jnp.float32)
    t = jnp.broadcast_to(t, logits.shape[:-1])[..., None]
    return jnp.where(t > 0, logits / jnp.where(t > 0, t, 1.0), logits)


def _sorted_desc(logits):
    return -jnp.sort(-logits, axis=-1)


def _top_k_thresh(sorted_desc, k):
    """The k-th largest value of each row, (..., 1); the row's smallest
    where ``k`` disables the filter, so that ``>=`` keeps everything."""
    vocab = sorted_desc.shape[-1]
    kk = jnp.asarray(k, jnp.int32)
    kk = jnp.broadcast_to(kk, sorted_desc.shape[:-1])
    kk = jnp.where((kk <= 0) | (kk > vocab), vocab, kk)
    return jnp.take_along_axis(sorted_desc, (kk - 1)[..., None], axis=-1)


def _top_p_thresh(sorted_desc, p):
    """The smallest value of each row's nucleus, (..., 1); ``-inf`` where
    ``p >= 1`` disables the filter."""
    pp = jnp.asarray(p, jnp.float32)
    pp = jnp.broadcast_to(pp, sorted_desc.shape[:-1])[..., None]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    # keep while the EXCLUSIVE prefix mass is still < p (so the token that
    # crosses the threshold is included), and always keep rank 0
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    keep = (exclusive < pp) | (
        jnp.arange(sorted_desc.shape[-1]) == 0)
    count = jnp.sum(keep.astype(jnp.int32), axis=-1, keepdims=True)
    thresh = jnp.take_along_axis(sorted_desc, count - 1, axis=-1)
    return jnp.where(pp >= 1, -jnp.inf, thresh)


def top_k_mask(logits, k):
    """Mask all but the top-k logits per row to :data:`NEG_INF`.

    ``k`` is a per-row int array (or scalar); ``k <= 0`` or ``k >= vocab``
    keeps the row unfiltered.  Ties with the k-th value are kept."""
    logits = jnp.asarray(logits, jnp.float32)
    thresh = _top_k_thresh(_sorted_desc(logits), k)
    return jnp.where(logits >= thresh, logits, NEG_INF)


def top_p_mask(logits, p):
    """Nucleus filtering: keep the smallest probability-sorted prefix with
    cumulative mass >= ``p`` (per-row array or scalar); the argmax token is
    always kept; ``p >= 1`` disables the filter (the row is returned as it
    came, whatever its tail's mass rounds to)."""
    logits = jnp.asarray(logits, jnp.float32)
    thresh = _top_p_thresh(_sorted_desc(logits), p)
    return jnp.where(logits >= thresh, logits, NEG_INF)


def top_k_top_p_mask(logits, k, p):
    """``top_p_mask(top_k_mask(logits, k), p)``, bit for bit, with one
    sort where that composition has two (per-row ``k`` and ``p``, or
    scalars): the top-k mask is a value threshold, so the sorted
    top-k-masked row is the sorted row with its tail replaced — the same
    values in the same order a second sort would give — and the nucleus
    is read from that."""
    logits = jnp.asarray(logits, jnp.float32)
    sorted_desc = _sorted_desc(logits)
    thresh_k = _top_k_thresh(sorted_desc, k)
    thresh_p = _top_p_thresh(
        jnp.where(sorted_desc >= thresh_k, sorted_desc, NEG_INF), p)
    return jnp.where(logits >= jnp.maximum(thresh_k, thresh_p), logits,
                     NEG_INF)


def fold_keys(seeds, counters):
    """Per-row PRNG keys from (request seed, token position) — a request's
    randomness depends only on its own seed and the position being sampled,
    NEVER on which decode slots it happens to share a batch with (the
    continuous-batching determinism contract)."""
    seeds = jnp.asarray(seeds, jnp.uint32)
    counters = jnp.asarray(counters, jnp.uint32)
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c)
    )(seeds, counters)


#: the bodies of :func:`sample_logits`, by the index :func:`sampler_body`
#: gives
SAMPLER_BODIES = ("greedy", "draw", "filter")


def sampler_body(temperature, top_k, top_p, vocab):
    """Which of :data:`SAMPLER_BODIES` a step with these per-row knobs
    takes, as an int32 scalar: 0 when no row samples, 1 when some row
    samples and none of those filters, 2 when a sampling row has ``0 <
    top_k < vocab`` or ``top_p < 1``.  One function over JAX arrays (the
    compiled program's ``lax.switch``) or NumPy arrays (the engine's
    count of the steps by body: the same arrays, the same answer)."""
    samples = temperature > 0
    filters = samples & (((top_k > 0) & (top_k < vocab)) | (top_p < 1))
    return samples.any().astype("int32") + filters.any().astype("int32")


def sample_logits(logits, seeds, counters, temperature, top_k, top_p):
    """One traced sampling step over a batch of logit rows.

    logits (B, V); seeds/counters/temperature/top_k/top_p all (B,).
    Rows with ``temperature <= 0`` take the raw argmax (greedy); the rest
    apply top-k then top-p filtering, temperature, and Gumbel-max draw.
    The step runs the body :func:`sampler_body` names, so it sorts only
    when a sampling row filters and draws noise only when a row samples;
    a row's token is the same whichever body its neighbours led to.
    Returns int32 token ids (B,).
    """
    logits = jnp.asarray(logits, jnp.float32)
    rows = logits.shape[:-1]
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), rows)
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), rows)
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), rows)

    def greedy(logits):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(logits, kept=None):
        # Gumbel-max over the kept logits / temperature, a row's noise
        # keyed on its (seed, counter) alone and drawn over the whole
        # vocabulary; a greedy row beside it takes the raw argmax
        scaled = temperature_scale(logits if kept is None else kept,
                                   temperature)
        gumbel = jax.vmap(
            lambda kd, row: jax.random.gumbel(kd, row.shape))(
                fold_keys(seeds, counters), scaled)
        sampled = jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy(logits))

    def filter_draw(logits):
        return draw(logits, top_k_top_p_mask(logits, top_k, top_p))

    return jax.lax.switch(
        sampler_body(temperature, top_k, top_p, logits.shape[-1]),
        (greedy, draw, filter_draw), logits)


def speculative_verify(logits, fed_tokens, seeds, counters, temperature,
                       top_k, top_p, lengths):
    """Vectorized draft verification for speculative decoding
    (docs/generation.md "Speculative decoding").

    One multi-query verify step fed row ``b`` the tokens
    ``[pending, d_1, .., d_s]`` at consecutive positions and produced
    per-position ``logits`` (B, T, V).  Because :func:`sample_logits` is
    keyed on ``(seed, position)`` only — Gumbel-max under
    :func:`fold_keys`, raw argmax for greedy rows — the TARGET model's
    token at every position is a deterministic function of (logits, seed,
    position), independent of how many positions are verified per step.
    Verification therefore reduces to exact match: draft ``d_j`` is
    accepted iff it equals the target's own sampled token at the position
    it was proposed for, cumulatively from the left.  Accepted tokens are
    bitwise the target-only stream for greedy rows and distribution-exact
    (literally the same draws) for stochastic rows.

    fed_tokens : (B, T) int32 — the chunk fed to the verify step
        (``fed_tokens[:, 0]`` is the pending token, columns ``1..`` the
        draft proposals, right-padded).
    counters : (B,) uint32 — index of the FIRST token being produced
        (``ctx + 1``, the same keying the single-step decode path uses);
        position ``j`` of the chunk samples with ``counters + j``.
    lengths : (B,) int32 — valid fed tokens per row (``s + 1``; 0 for
        inactive slots).

    Returns ``(target_tokens (B, T) int32, accepted (B,) int32)``:
    ``target_tokens[b, j]`` is the target's token for produced index
    ``counters[b] + j``; ``accepted[b]`` counts the leading drafts that
    matched, so the row may emit ``accepted[b] + 1`` tokens (the matched
    drafts plus the first non-matching target token — the "bonus" token
    when every draft matched).  Entries past ``lengths`` are garbage.
    """
    logits = jnp.asarray(logits, jnp.float32)
    B, T, _ = logits.shape
    rep = lambda a, dt: jnp.broadcast_to(  # noqa: E731
        jnp.asarray(a, dt)[:, None], (B, T)).reshape(-1)
    ctr = (jnp.asarray(counters, jnp.uint32)[:, None]
           + jnp.arange(T, dtype=jnp.uint32)[None, :])
    target = sample_logits(
        logits.reshape(B * T, -1), rep(seeds, jnp.uint32),
        ctr.reshape(-1), rep(temperature, jnp.float32),
        rep(top_k, jnp.int32), rep(top_p, jnp.float32)).reshape(B, T)
    if T == 1:
        return target, jnp.zeros((B,), jnp.int32)
    # draft j (fed column j) is checked against the target token sampled
    # at the PREVIOUS column; cumprod keeps only the leading run
    match = (fed_tokens[:, 1:] == target[:, :-1])
    valid = (jnp.arange(T - 1, dtype=jnp.int32)[None, :]
             < (jnp.asarray(lengths, jnp.int32) - 1)[:, None])
    ok = (match & valid).astype(jnp.int32)
    accepted = jnp.sum(jnp.cumprod(ok, axis=1), axis=1).astype(jnp.int32)
    return target, accepted


# -- registry entries (scalar-attr op forms) ---------------------------------------
@register("_sampling_greedy", differentiable=False,
          aliases=("sample_greedy",))
def sampling_greedy(logits):
    """Greedy decoding: per-row argmax token ids (int32)."""
    return jnp.argmax(jnp.asarray(logits, jnp.float32),
                      axis=-1).astype(jnp.int32)


@register("_sampling_temperature", rng=True, differentiable=False,
          aliases=("sample_temperature",))
def sampling_temperature(logits, rng_key=None, temperature=1.0):
    """Temperature sampling: Gumbel-max over ``logits / temperature``;
    ``temperature <= 0`` falls back to greedy."""
    logits = jnp.asarray(logits, jnp.float32)
    if float(temperature) <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = temperature_scale(logits, float(temperature))
    gumbel = jax.random.gumbel(rng_key, logits.shape)
    return jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)


@register("_sampling_top_k", rng=True, differentiable=False,
          aliases=("sample_top_k",))
def sampling_top_k(logits, rng_key=None, k=0, temperature=1.0):
    """Top-k sampling: mask to the k largest logits per row, then
    temperature-sample (``k <= 0`` disables the filter)."""
    return sampling_temperature(top_k_mask(logits, int(k)), rng_key=rng_key,
                                temperature=temperature)


@register("_sampling_top_p", rng=True, differentiable=False,
          aliases=("sample_top_p",))
def sampling_top_p(logits, rng_key=None, p=1.0, temperature=1.0):
    """Nucleus (top-p) sampling: mask to the smallest probability prefix
    with mass >= p, then temperature-sample (``p >= 1`` disables)."""
    return sampling_temperature(top_p_mask(logits, float(p)), rng_key=rng_key,
                                temperature=temperature)


def block_unmask(logits, masked, n_unmask):
    """The choice a denoise pass of generation by diffusion over blocks
    makes (greedy, ``low_confidence_static`` remasking; docs/generation.md
    "Block-diffusion generation"), with no sort over the vocabulary.

    ``logits`` (S, L, vocab): the block's own positions' logits; ``masked``
    (S, L) bool: which positions still hold MASK; ``n_unmask`` (S,) int:
    how many of them this pass unmasks.  At every position ``x0`` is the
    argmax and its confidence the softmax probability of ``x0`` (``1 /
    sum(exp(logits - max))``); a row's ``n_unmask`` most confident masked
    positions (ties: the lower position) take their ``x0``.  Returns (S,
    L) int32: the new token id there, -1 everywhere else."""
    logits = jnp.asarray(logits, jnp.float32)
    L = logits.shape[1]
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(logits - top), axis=-1)       # (S, L)
    conf = jnp.where(masked, conf, -jnp.inf)
    n = jnp.asarray(n_unmask, jnp.int32)
    at = jnp.arange(L, dtype=jnp.int32)[None, :]
    chosen = jnp.zeros(conf.shape, bool)
    for j in range(L):
        # argmax returns the first maximum: the lower position on a tie
        pick = (at == jnp.argmax(conf, axis=1)[:, None]) \
            & (jnp.max(conf, axis=1) > -jnp.inf)[:, None] & (j < n)[:, None]
        chosen = chosen | pick
        conf = jnp.where(pick, -jnp.inf, conf)
    return jnp.where(chosen, x0, -1)
