"""The models' attention cores against an independent float64 oracle.

``attention_core`` materializes the (Tq, Tk) scores; ``attention_core_blocked``
(the path of every train step above ``BLOCKED_ATTENTION_THRESHOLD`` query
positions, as in the Mistral cells) slices q into blocks and each block's
causal/window kv prefix statically.  Both are checked against a plain numpy
masked softmax in float64 that shares no code with them: heads grouped by
repeating each kv head over its query heads, the causal mask ``kp <= qp``
and the window mask ``kp > qp - window``.  The blocked core runs with
``q_block=32`` so that every case's causal and window cuts cross block
edges."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention

Q_BLOCK = 32

CORES = {
    "full": attention.attention_core,
    "blocked": functools.partial(attention.attention_core_blocked,
                                 q_block=Q_BLOCK),
}

CASES = [
    # (B, T, H, KV, hd, causal, window)
    (2, 64, 4, 2, 32, True, None),
    (2, 100, 4, 1, 32, True, None),      # T not a multiple of the block
    (2, 64, 4, 4, 32, False, None),      # bidirectional, no grouping
    (1, 96, 6, 2, 16, True, 32),         # window + GQA + odd heads
    (1, 128, 32, 8, 16, True, 64),       # Mistral's 4:1 heads, window < T
    (1, 128, 8, 2, 16, True, 128),       # window >= T, as in the LM cells
]


def _allowed(T, causal, window):
    """(T, T) bool: query position qp may attend key position kp."""
    qp = np.arange(T)[:, None]
    kp = np.arange(T)[None, :]
    ok = np.ones((T, T), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    return ok


def _oracle(q, k, v, causal, window):
    """Masked softmax attention in float64 numpy: q (B, T, H, hd),
    k/v (B, T, KV, hd) -> (B, T, H, hd)."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    T, H, hd = q.shape[1], q.shape[2], q.shape[3]
    group = H // k.shape[2]
    k = np.repeat(k, group, axis=2)      # kv head j serves heads j*G..j*G+G-1
    v = np.repeat(v, group, axis=2)
    scores = np.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(hd)
    scores = np.where(_allowed(T, causal, window), scores, -np.inf)
    scores -= scores.max(axis=-1, keepdims=True)
    probs = np.exp(scores)
    probs /= probs.sum(axis=-1, keepdims=True)
    return np.einsum("bhqs,bshd->bqhd", probs, v)


def _qkv(B, T, H, KV, hd, seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=s).astype(np.float32))
                 for s in ((B, T, H, hd), (B, T, KV, hd), (B, T, KV, hd)))


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
@pytest.mark.parametrize("core", sorted(CORES))
def test_attention_core_matches_float64_oracle(core, case):
    B, T, H, KV, hd, causal, window = case
    q, k, v = _qkv(B, T, H, KV, hd, seed=T * H + hd)
    out = CORES[core](q, k, v, causal=causal, sliding_window=window)
    assert out.shape == (B, T, H, hd) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               _oracle(q, k, v, causal, window),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("core", sorted(CORES))
def test_attention_core_rows_are_convex_combos(core):
    """Each output row is a convex combination of the v rows its query may
    see: inside their per-feature [min, max], and exactly 1 where v is."""
    B, T, H, KV, hd, causal, window = 2, 64, 4, 2, 16, True, 24
    q, k, v = _qkv(B, T, H, KV, hd, seed=0)
    out = np.asarray(CORES[core](q, k, v, causal=causal,
                                 sliding_window=window), np.float64)
    vg = np.repeat(np.asarray(v, np.float64), H // KV, axis=2)
    seen = _allowed(T, causal, window)[None, :, :, None, None]
    lo = np.where(seen, vg[:, None], np.inf).min(axis=2)     # (B, T, H, hd)
    hi = np.where(seen, vg[:, None], -np.inf).max(axis=2)
    assert np.all(out >= lo - 1e-5) and np.all(out <= hi + 1e-5)
    ones = CORES[core](q, k, jnp.ones_like(v), causal=causal,
                       sliding_window=window)
    np.testing.assert_allclose(np.asarray(ones), 1.0, rtol=1e-6)
