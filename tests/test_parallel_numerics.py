"""Distributed-numerics equality: every shard_map region and the full model
must produce identical results with and without a mesh (subprocess with 8
virtual devices; this is what makes the 512-chip dry-run trustworthy)."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    key = jax.random.PRNGKey(0)

    # --- sequence-parallel attention == dense ------------------------------
    from repro.models import attention
    from repro.models.attention import AttentionSpec
    spec = AttentionSpec(d_model=64, num_heads=6, num_kv_heads=2, head_dim=16,
                         qkv_bias=True, qk_norm=True)
    p = attention.init(key, spec, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 64))
    ref = attention.apply(p, spec, x)
    def loss_sp(p, x):
        return jnp.sum(attention.apply_sequence_parallel(
            p, spec, x, q_block=32, kv_block=32) ** 2)
    g_ref = jax.grad(lambda p, x: jnp.sum(attention.apply(p, spec, x)**2))(p, x)
    with jax.set_mesh(mesh):
        sp = jax.jit(lambda pp, xx: attention.apply_sequence_parallel(
            pp, spec, xx, q_block=32, kv_block=32))(p, x)
        g_sp = jax.jit(jax.grad(loss_sp))(p, x)
    assert float(jnp.max(jnp.abs(ref - sp))) < 1e-4, "SP attention fwd"
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sp)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3, "SP attention grad"

    # --- expert-parallel MoE == dense --------------------------------------
    from repro.models import moe
    mspec = moe.MoESpec(d_model=32, d_ff=64, num_experts=8,
                        experts_per_token=2, capacity_factor=8.0)
    mp = moe.init(key, mspec, dtype=jnp.float32)
    xm = jax.random.normal(jax.random.PRNGKey(2), (8, 16, 32))
    out_ref, aux_ref = moe._apply_dense(mp, mspec, xm)
    def mloss(p, xx):
        o, a = moe.apply(p, mspec, xx)
        return jnp.sum(o ** 2) + a
    gm_ref = jax.grad(mloss)(mp, xm)
    with jax.set_mesh(mesh):
        out_ep, aux_ep = jax.jit(lambda p, xx: moe.apply(p, mspec, xx))(mp, xm)
        gm_ep = jax.jit(jax.grad(mloss))(mp, xm)
    assert float(jnp.max(jnp.abs(out_ref - out_ep))) < 1e-5, "EP fwd"
    assert abs(float(aux_ref) - float(aux_ep)) < 1e-5, "EP aux"
    for a, b in zip(jax.tree.leaves(gm_ref), jax.tree.leaves(gm_ep)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3, "EP grad"

    # --- Megatron SP+TP swiglu == local -------------------------------------
    from repro.models import layers
    sp_params = layers.swiglu_init(jax.random.PRNGKey(3), 64, 128,
                                   dtype=jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(4), (4, 128, 64))
    ref_s = layers._swiglu_local(sp_params["w_gate"], sp_params["w_up"],
                                 sp_params["w_down"], xs)
    def sloss(p, xx):
        return jnp.sum(layers.swiglu(p, xx) ** 2)
    gs_ref = jax.grad(lambda p, xx: jnp.sum(layers._swiglu_local(
        p["w_gate"], p["w_up"], p["w_down"], xx) ** 2))(sp_params, xs)
    with jax.set_mesh(mesh):
        out_s = jax.jit(lambda p, xx: layers.swiglu(p, xx))(sp_params, xs)
        gs = jax.jit(jax.grad(sloss))(sp_params, xs)
    assert float(jnp.max(jnp.abs(ref_s - out_s))) < 1e-4, "swiglu fwd"
    for a, b in zip(jax.tree.leaves(gs_ref), jax.tree.leaves(gs)):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4, "swiglu grad"

    # --- sharded chunked WKV == sequential scan -----------------------------
    from repro.models import rwkv
    B, T, H, hd = 8, 128, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd))))
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    S0 = jax.random.normal(jax.random.PRNGKey(6), (B, H, hd, hd))
    y_ref, f_ref = rwkv.wkv_scan(r, k, v, w, u, S0)
    with jax.set_mesh(mesh):
        y, f = jax.jit(rwkv._wkv_dispatch)(r, k, v, w, u, S0)
    assert float(jnp.max(jnp.abs(y_ref - y))) < 1e-3, "wkv"
    assert float(jnp.max(jnp.abs(f_ref - f))) < 1e-3, "wkv state"

    # --- full reduced model: loss under mesh == loss without ---------------
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("minitron-4b").reduced()
    params = M.init(jax.random.PRNGKey(7), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(8), (8, 64),
                             0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, -1)}
    l_ref = float(M.loss_fn(params, batch, cfg))
    with jax.set_mesh(mesh):
        l_mesh = float(jax.jit(
            lambda p, b: M.loss_fn(p, b, cfg))(params, batch))
    assert abs(l_ref - l_mesh) < 1e-3, (l_ref, l_mesh)
    print("OK")
""")


def test_parallel_numerics():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-4000:])
    assert "OK" in res.stdout
