"""Token batches for the expert-layer cells, made on the device.

``tokens.lm_batch`` with its Zipf-like half drawn uniformly over the
vocabulary slice instead; the bigram walk half is the same.  The reason is
the router at random weights: it routes a token by its identity, so
``tokens.lm_batch``'s unigram (about 31% of all ids are 0) sends a third of
the tokens to one set of experts, and whether those are held by the chip is
a draw of the seed (0.64 to 0.93 held assignments a token over five seeds
on a v5e), a load no trained DeepSeek-V3 router shows, its selection
bias keeping every expert near the mean.  Distinct ids give each token a
route of its own, as context does in a trained model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lm_batch(key, step, *, vocab_size: int, seq_len: int, groups: int,
             per_group: int):
    """``{"tokens", "labels"}``, each (groups, per_group, seq_len) int32."""
    key = jax.random.fold_in(key, step)
    k_uni, k_mix, k_start = jax.random.split(key, 3)
    shape = (groups, per_group, seq_len)
    uniform = jax.random.randint(k_uni, shape, 0, vocab_size)
    start = jax.random.randint(k_start, shape[:2] + (1,), 0, vocab_size)
    pos = jnp.arange(seq_len, dtype=jnp.int32)[None, None, :]
    bigram = (start * 31 + pos * 7919) % vocab_size
    mix = jax.random.bernoulli(k_mix, 0.5, shape)
    tokens = jnp.where(mix, uniform, bigram).astype(jnp.int32)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1)}
