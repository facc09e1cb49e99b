"""Unit tests: layers, optimizers, schedules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.models import layers
from repro.optim import schedule


def test_rmsnorm_matches_naive():
    p = layers.rmsnorm_init(8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8))
    out = layers.rmsnorm(p, x)
    naive = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True)
                        + 1e-5)
    np.testing.assert_allclose(np.asarray(out), naive, atol=1e-5)


def test_layernorm_zero_mean_unit_var():
    p = layers.layernorm_init(16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16)) * 5 + 3
    out = np.asarray(layers.layernorm(p, x))
    np.testing.assert_allclose(out.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(out.std(-1), 1.0, atol=1e-2)


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 8))
    pos = jnp.broadcast_to(jnp.arange(6)[None], (1, 6))
    out = layers.apply_rope(x, pos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 8))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 8))
    def dot_at(i, j):
        qi = layers.apply_rope(q, jnp.full((1, 1), i))
        kj = layers.apply_rope(k, jnp.full((1, 1), j))
        return float(jnp.sum(qi * kj))
    assert abs(dot_at(3, 1) - dot_at(7, 5)) < 1e-4
    assert abs(dot_at(3, 1) - dot_at(3, 2)) > 1e-6


def test_rope_position_zero_identity():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 8))
    out = layers.apply_rope(x, jnp.zeros((1, 1)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


def test_cross_entropy_matches_naive():
    V, B, T, D = 11, 2, 8, 4
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (D, V))
    h = jax.random.normal(jax.random.fold_in(key, 1), (B, T, D))
    y = jax.random.randint(jax.random.fold_in(key, 2), (B, T), 0, V)
    out = layers.cross_entropy_loss(lambda hh: hh @ W, h, y, vocab_chunk=4)
    logits = np.asarray(h @ W)
    lse = np.log(np.sum(np.exp(logits - logits.max(-1, keepdims=True)), -1)) \
        + logits.max(-1)
    picked = np.take_along_axis(logits, np.asarray(y)[..., None], -1)[..., 0]
    naive = float(np.mean(lse - picked))
    assert abs(float(out) - naive) < 1e-4


def test_cross_entropy_ignore_index():
    V, D = 7, 4
    W = jnp.eye(D, V)
    h = jnp.ones((1, 4, D))
    y = jnp.array([[1, -1, -1, 2]])
    out = layers.cross_entropy_loss(lambda hh: hh @ W, h, y, vocab_chunk=2)
    y2 = jnp.array([[1, 2, 1, 2]])
    out2 = layers.cross_entropy_loss(lambda hh: hh @ W, h, y2, vocab_chunk=2)
    assert jnp.isfinite(out)
    # uniform h => same per-token loss; masking shouldn't change the mean
    np.testing.assert_allclose(float(out), float(out2), atol=1e-5)


def test_sgd_matches_manual():
    opt = optim.sgd(0.1)
    params = {"w": jnp.array([1.0, 2.0])}
    state = opt.init(params)
    grads = {"w": jnp.array([0.5, -1.0])}
    updates, state = opt.update(grads, state, params)
    np.testing.assert_allclose(np.asarray(updates["w"]), [-0.05, 0.1],
                               atol=1e-7)
    assert int(state.step) == 1


def test_sgd_momentum_accumulates():
    opt = optim.sgd(1.0, momentum=0.9)
    params = {"w": jnp.zeros((1,))}
    state = opt.init(params)
    g = {"w": jnp.ones((1,))}
    u1, state = opt.update(g, state, params)
    u2, state = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(u1["w"]), [-1.0])
    np.testing.assert_allclose(np.asarray(u2["w"]), [-1.9])


def test_adamw_first_step_is_lr_sized():
    opt = optim.adamw(1e-2, weight_decay=0.0)
    params = {"w": jnp.array([10.0])}
    state = opt.init(params)
    updates, _ = opt.update({"w": jnp.array([3.0])}, state, params)
    np.testing.assert_allclose(np.asarray(updates["w"]), [-1e-2], rtol=1e-3)


def test_adamw_grad_clip():
    opt = optim.adamw(1.0, grad_clip_norm=1.0)
    params = {"w": jnp.zeros((2,))}
    state = opt.init(params)
    big = {"w": jnp.array([300.0, 400.0])}    # norm 500 -> scaled to 1
    _, state2 = opt.update(big, state, params)
    np.testing.assert_allclose(float(jnp.linalg.norm(state2.mu["w"])),
                               0.1, rtol=1e-4)   # (1-b1)*clipped


def test_schedules():
    s = schedule.warmup_cosine(1.0, warmup_steps=10, total_steps=100)
    assert float(s(jnp.asarray(0))) == 0.0
    assert abs(float(s(jnp.asarray(10))) - 1.0) < 1e-5
    assert float(s(jnp.asarray(100))) <= 0.11
    inv = schedule.inverse_sqrt(1.0, warmup_steps=16)
    assert float(inv(jnp.asarray(16))) == 1.0
    assert abs(float(inv(jnp.asarray(64))) - 0.5) < 1e-5
    # linear_warmup moves from the first step
    s = schedule.linear_warmup(1e-3, warmup_steps=1000)
    assert np.isclose(float(s(jnp.asarray(0))), 1e-6)
    assert np.isclose(float(s(jnp.asarray(499))), 5e-4)
    assert np.isclose(float(s(jnp.asarray(999))), 1e-3)
    assert np.isclose(float(s(jnp.asarray(5000))), 1e-3)
