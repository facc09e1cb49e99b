"""Token batches for the language-model cells, made on the device.

A copy of the program's synthetic stream (``data/tokens.TokenStream.batch``)
kept with the benchmark, so that the traffic a cell measures cannot change
with the program.  Tokens are a half-and-half mix of a Zipf-like unigram
draw and a deterministic bigram walk (t_{i+1} = 31 t_0 + 7919 i mod V), so
that the loss is learnable; labels are the tokens shifted by one.  Every
step's rows are drawn afresh from ``fold_in(key, step)``: no two steps and
no two rows share their tokens.
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
    u = jax.random.uniform(k_uni, shape, minval=1e-6, maxval=1.0)
    zipf = jnp.clip((u ** (-0.7) - 1.0).astype(jnp.int32), 0, vocab_size - 1)
    start = jax.random.randint(k_start, shape[:2] + (1,), 0, vocab_size)
    pos = jnp.arange(seq_len, dtype=jnp.int32)[None, None, :]
    bigram = (start * 31 + pos * 7919) % vocab_size
    mix = jax.random.bernoulli(k_mix, 0.5, shape)
    tokens = jnp.where(mix, zipf, bigram).astype(jnp.int32)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1)}
