"""The DeepSeek-V3 block (Moonlight-16B-A3B's): latent attention, the
sigmoid-routed dropless expert layer over the experts a chip holds, and the
leading dense layer, against the plain reference
(``bench/reference/mla_moe_lm.py``) at a small size, in float32 on the
CPU, on seeded random weights."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import mla_moe_lm as ref  # noqa: E402
from bench.reference.dense_lm import _mm  # noqa: E402
from repro.configs.moonlight_16b_a3b import CHIP_SHARE  # noqa: E402
from repro.models import attention, blocks, layers, moe  # noqa: E402
from repro.models import model as M  # noqa: E402

MM = _mm("f32")
# float32 on both sides; what is left is the order of the sums
TOL = dict(rtol=2e-5, atol=2e-6)


def small(**kw):
    base = dict(d_model=64, num_heads=4, num_kv_heads=4, d_ff=32,
                dense_d_ff=128, vocab_size=256, num_layers=3,
                first_dense_layers=1, num_experts=8, experts_per_token=3,
                experts_held=2, experts_held_lo=0, kv_lora_rank=16,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=8,
                dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
                loss_chunk=0)
    base.update(kw)
    return CHIP_SHARE.with_(**base)


def ref_cfg(cfg) -> dict:
    """The reference's configuration, under the published keys."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "n_routed_experts_published": cfg.num_experts,
            "n_routed_experts": cfg.experts_held or cfg.num_experts,
            "experts_held_from": cfg.experts_held_lo,
            "num_experts_per_tok": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scaling,
            "balance_alpha": cfg.balance_alpha}


def normal(seed, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape)


def test_latent_attention_matches_reference():
    cfg = small()
    spec = blocks.mla_spec(cfg)
    p = attention.mla_init(jax.random.PRNGKey(0), spec, dtype=jnp.float32)
    x = normal(1, (2, 48, cfg.d_model))
    np.testing.assert_allclose(attention.mla_apply(p, spec, x),
                               ref._latent_attention(x, p, ref_cfg(cfg), MM),
                               **TOL)


@pytest.mark.parametrize("blocked", [False, True])
def test_attention_core_takes_a_value_head_dim_of_its_own(blocked):
    """q/k of 24 dims, v of 8: the output takes v's head dim, and the
    blocked core (three q blocks) agrees with a plain masked softmax."""
    q, k, v = normal(2, (2, 48, 4, 24)), normal(3, (2, 48, 4, 24)), \
        normal(4, (2, 48, 4, 8))
    if blocked:
        out = attention.attention_core_blocked(q, k, v, causal=True,
                                               sliding_window=None,
                                               q_block=16)
    else:
        out = attention.attention_core(q, k, v, causal=True,
                                       sliding_window=None)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 24 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    assert out.shape == (2, 48, 4, 8)
    np.testing.assert_allclose(out, want, **TOL)


def _expert_layer(cfg, routing):
    spec = blocks.deepseek_moe_spec(cfg)
    p = moe.deepseek_init(jax.random.PRNGKey(5), spec, dtype=jnp.float32)
    if routing == "one_expert":
        # the selection bias sends every token to held expert 1
        p["router_bias"] = p["router_bias"].at[cfg.experts_held_lo + 1].set(
            10.0)
    elif routing == "bias":
        p["router_bias"] = normal(6, (cfg.num_experts,), 0.5)
    return spec, p


@pytest.mark.parametrize("routing", ["random", "one_expert", "bias"])
def test_expert_layer_matches_reference(routing):
    cfg = small(experts_held_lo=2)
    spec, p = _expert_layer(cfg, routing)
    x = normal(7, (2, 40, cfg.d_model))
    out, balance, loads = moe.deepseek_apply(p, spec, x)
    want, want_balance = ref._experts(x, p, ref_cfg(cfg), MM)
    np.testing.assert_allclose(out, want, **TOL)
    np.testing.assert_allclose(balance, want_balance, rtol=1e-5)
    ids, _, _ = ref.route(x, p, ref_cfg(cfg), MM)
    local = np.asarray(ids).reshape(-1) - cfg.experts_held_lo
    np.testing.assert_array_equal(
        loads, np.bincount(local[(local >= 0) & (local < spec.held)],
                           minlength=spec.held))
    if routing == "one_expert":
        # dropless: all 80 tokens on one expert, 2.7x a 1.0-capacity share
        assert int(loads[1]) == x.shape[0] * x.shape[1]


def test_selection_bias_picks_experts_but_never_weighs_them():
    cfg = small()
    spec, p = _expert_layer(cfg, "bias")
    x = normal(8, (2, 40, cfg.d_model))
    ids, w, _ = moe.deepseek_route(p, spec, x)
    ids0, _, _ = moe.deepseek_route(dict(p, router_bias=0 * p["router_bias"]),
                                    spec, x)
    assert np.any(np.sort(ids, -1) != np.sort(ids0, -1))
    scores = jax.nn.sigmoid(x.reshape(-1, cfg.d_model) @ p["router"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scaling,
        rtol=1e-6)


def test_held_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 shares of 2: the shares' outputs summed, with the
    shared experts every share computes counted once, are the uncut
    layer's, in the program and in the reference."""
    cfg = small(experts_held=8)
    spec, p = _expert_layer(cfg, "random")
    x = normal(9, (2, 40, cfg.d_model))
    total = 0.0
    for lo in (0, 2, 4, 6):
        share = dataclasses.replace(spec, held=2, held_lo=lo)
        ps = dict(p, experts=jax.tree.map(lambda w: w[lo:lo + 2],
                                          p["experts"]))
        total = total + moe.deepseek_apply(ps, share, x)[0]
    shared = layers.swiglu(p["shared"], x)
    uncut = moe.deepseek_apply(p, spec, x)[0]
    np.testing.assert_allclose(total - 3 * shared, uncut, **TOL)
    np.testing.assert_allclose(uncut, ref._experts(x, p, ref_cfg(cfg),
                                                   MM)[0], **TOL)


@pytest.mark.parametrize("remat,policy", [
    pytest.param(False, None, id="False"),
    pytest.param(True, None, id="True"),
    pytest.param(True, blocks.SAVE_DOTS, id="dots")])
def test_model_loss_and_grads_match_reference(remat, policy):
    cfg = small(remat=remat)
    params = M.init(jax.random.PRNGKey(10), cfg)
    params["layers"]["moe"]["router_bias"] = normal(
        11, params["layers"]["moe"]["router_bias"].shape, 0.3)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 32), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=-1)
    with blocks.remat_policy(policy):
        loss, grads = jax.value_and_grad(M.loss_fn)(
            params, {"tokens": tokens, "labels": labels}, cfg)
    want, want_grads = jax.value_and_grad(ref.group_loss)(
        params, tokens, labels, ref_cfg(cfg))
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    # the selection bias takes no gradient
    assert not np.any(grads["layers"]["moe"]["router_bias"])


def test_param_count_is_what_init_makes():
    cfg = small()
    params = jax.eval_shape(lambda k: M.init(k, cfg), jax.random.PRNGKey(0))
    assert sum(l.size for l in jax.tree.leaves(params)) == cfg.param_count()
    share = jax.eval_shape(lambda k: M.init(k, CHIP_SHARE),
                           jax.random.PRNGKey(0))
    # 568,484,352 trained and 4 x 64 selection biases
    assert sum(l.size for l in jax.tree.leaves(share)) == 568_484_608
    assert CHIP_SHARE.param_count() == 568_484_608
