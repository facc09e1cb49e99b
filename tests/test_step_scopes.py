"""The robust step names its layers, and counts its Weiszfeld steps.

Every layer of the group step and of the scanned round runner runs under a
``jax.named_scope``; the scope lands in the ``op_name`` metadata of each
instruction of the compiled module, which is how a profile of the step is
split by layer.  The step's ``weiszfeld_iters`` output is the reference
geometric median's final loop counter."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config
from repro.core import RobustConfig, make_run_rounds
from repro.data import regression
from repro.data.tokens import TokenStream
from repro.launch import steps
from repro.models import model as model_lib
from repro.roofline.hlo_parser import op_names

STEP_SCOPES = ("group_fwd_bwd", "attack", "aggregate", "optimizer",
               "step_metrics", "batch_means", "trim", "weiszfeld", "gram")
ROUND_SCOPES = ("worker_grads", "attack", "aggregate", "optimizer",
                "step_metrics", "batch_means", "trim", "weiszfeld", "gram")
# scopes whose ops are elementwise, so that the compiler may fuse them into
# their consumers and name no op of its own after them: the Weiszfeld
# combine's multiply-add chain (into the step's metrics and update)
FUSED_SCOPES = ("combine",)
# the DeepSeek-V3 block's layers, inside the group step's fwd/bwd
MOE_SCOPES = ("mla", "router", "dispatch", "experts", "shared_experts")
ALL_SCOPES = set(STEP_SCOPES + ROUND_SCOPES + FUSED_SCOPES + MOE_SCOPES) | {
    "encode", "decode", "round_kernel"}
# what the CPU compiler leaves outside every scope: the arguments, and
# instructions it makes itself, without an op_name of a traced operation
COMPILER_MADE = {"parameter", "constant", "tuple", "get-tuple-element",
                 "copy", "bitcast", "fusion"}
K = 4


def _tiny(k=K, **rc_kw):
    cfg = get_config("minitron-4b").reduced()
    kw = dict(num_workers=k, num_byzantine=1, num_batches=k,
              attack="sign_flip", aggregator="gmom")
    kw.update(rc_kw)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=2 * k, num_workers=k, seed=0)
    return cfg, RobustConfig(**kw), stream


def _tiny_moe(k=K):
    """The DeepSeek-V3 block at a test size: a dense layer and two expert
    layers of 8 experts, top-3, of which experts 2-3 are held."""
    from repro.configs.moonlight_16b_a3b import CHIP_SHARE
    cfg = CHIP_SHARE.reduced().with_(num_layers=3, num_experts=8,
                                     experts_per_token=3, experts_held=2,
                                     experts_held_lo=2)
    rc = RobustConfig(num_workers=k, num_byzantine=1, num_batches=k,
                      attack="sign_flip", aggregator="gmom")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=2 * k, num_workers=k, seed=0)
    return cfg, rc, stream


def _compiled_step(cfg, rc, stream):
    opt = optim.adamw(1e-3)
    params_s = jax.eval_shape(lambda kk: model_lib.init(kk, cfg),
                              jax.random.PRNGKey(0))
    opt_s = jax.eval_shape(opt.init, params_s)
    batch_s = jax.eval_shape(stream.batch, 0)
    jitted, _ = steps.jit_group_train_step(cfg, rc, opt, params_s, opt_s,
                                           batch_s)
    return jitted.lower(params_s, opt_s, batch_s, jax.random.PRNGKey(0),
                        jnp.int32(0)).compile()


def scope_problems(hlo_text, expected, fused=()):
    """``(scopes missing, traced instructions under no scope, unscoped
    instructions of other kinds)`` of a compiled module's text.  A scope of
    ``expected`` is missing without an op of its own under it; one of
    ``fused`` without a traced operation under it, fused or not."""
    rows = op_names(hlo_text)
    seen = {part for _, _, path in rows if path
            for part in path.split("/")}
    traced_ops = {part for path in re.findall(r'op_name="([^"]*)"', hlo_text)
                  for part in path.split("/")}
    missing = [s for s in expected if s not in seen] + [
        s for s in fused if s not in traced_ops]
    traced, other = [], []
    for name, opcode, path in rows:
        if path and ALL_SCOPES & set(path.split("/")):
            continue
        if path and path.startswith("jit("):
            traced.append((name, path))
        elif opcode not in COMPILER_MADE or (opcode == "fusion" and path):
            other.append((name, opcode, path))
    return missing, traced, other


@pytest.fixture(scope="module")
def gmom_step_text():
    # four groups in two batches, so that the batch means are computed
    return _compiled_step(*_tiny(num_batches=2)).as_text()


def test_every_layer_of_the_group_step_is_scoped(gmom_step_text):
    missing, traced, other = scope_problems(gmom_step_text, STEP_SCOPES,
                                            FUSED_SCOPES)
    assert missing == []
    assert traced == []
    assert other == []


@pytest.mark.parametrize("scope", ["gram", "combine"])
def test_weiszfeld_passes_nest_under_weiszfeld(gmom_step_text, scope):
    """The Gram and combine passes are parts of the ``weiszfeld`` layer: every
    traced operation of theirs, fused or not, carries ``weiszfeld/<scope>``."""
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"',
                                              gmom_step_text)
             if scope in p.split("/")]
    assert paths
    for parts in paths:
        assert parts[parts.index(scope) - 1] == "weiszfeld", parts


@pytest.mark.parametrize("renamed, outermost", [("optimizer", True),
                                                 ("weiszfeld", False)])
def test_a_dropped_or_renamed_scope_is_caught(gmom_step_text, renamed,
                                              outermost):
    doctored = gmom_step_text.replace(f"/{renamed}/", "/")
    missing, traced, _ = scope_problems(doctored, STEP_SCOPES)
    assert missing == [renamed]
    # a layer of the step's own leaves its ops under no scope at all
    assert bool(traced) == outermost
    doctored = gmom_step_text.replace(f"/{renamed}/", f"/{renamed}_x/")
    assert scope_problems(doctored, STEP_SCOPES)[0] == [renamed]


@pytest.fixture(scope="module")
def moe_step_text():
    return _compiled_step(*_tiny_moe()).as_text()


def test_expert_layers_are_scoped_under_fwd_bwd(moe_step_text):
    missing, traced, _ = scope_problems(
        moe_step_text, ("group_fwd_bwd", "aggregate", "optimizer")
        + MOE_SCOPES)
    assert missing == [] and traced == []
    # every traced operation's path (a reduction's inner computation
    # carries its scope alone)
    for path in re.findall(r'op_name="(jit\([^"]*)"', moe_step_text):
        parts = path.split("/")
        for scope in set(MOE_SCOPES).intersection(parts):
            assert "group_fwd_bwd" in parts[:parts.index(scope)], parts


def test_router_counters_are_the_reference_routing():
    """``moe_local_assignments`` and ``moe_load_max`` are what the plain
    reference's routing of the step's k groups gives."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.reference import mla_moe_lm
    from test_mla_moe import ref_cfg
    cfg, rc, stream = _tiny_moe()
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    batch = stream.batch(0)
    opt = optim.sgd(0.1)
    _, _, metrics = jax.jit(steps.make_group_train_step(cfg, rc, opt))(
        params, opt.init(params), batch, jax.random.PRNGKey(5), jnp.int32(0))
    ref = dict(ref_cfg(cfg), first_k_dense_replace=cfg.first_dense_layers)
    cfg_t = tuple(sorted(ref.items()))
    loads = 0
    for g in range(K):
        ids = np.asarray(mla_moe_lm.routing(params, batch["tokens"][g],
                                            cfg_t)) - cfg.experts_held_lo
        loads = loads + np.stack([np.bincount(
            i[(i >= 0) & (i < cfg.experts_held)], minlength=cfg.experts_held)
            for i in ids.reshape(ids.shape[0], -1)])
    assert int(metrics["moe_local_assignments"]) == loads.sum() > 0
    np.testing.assert_allclose(
        metrics["moe_load_max"], np.max(loads.max(-1) / loads.mean(-1)),
        rtol=1e-6)


def test_wire_codec_is_scoped():
    text = _compiled_step(*_tiny(compression="int8_stochastic")).as_text()
    # the elementwise decode fuses into its readers: the trim norms, the
    # Gram pass and the combine
    missing, traced, other = scope_problems(
        text, ("encode", "aggregate", "weiszfeld"), fused=("decode",))
    assert missing == [] and traced == [] and other == []
    # with no op of its own, decode is timed only inside its readers: each
    # of its traced operations must still sit directly under ``aggregate``
    for path in re.findall(r'op_name="([^"]*)"', text):
        parts = path.split("/")
        if "decode" in parts:
            assert parts[parts.index("decode") - 1] == "aggregate", parts


def _linreg_runner(round_backend):
    d, m = 16, 10
    ds = regression.generate(jax.random.PRNGKey(1), dim=d, total_samples=400,
                             num_workers=m)
    rc = RobustConfig(num_workers=m, num_byzantine=2, num_batches=5,
                      attack="sign_flip", aggregator="gmom",
                      round_backend=round_backend)
    opt = optim.sgd(0.5)
    run = make_run_rounds(regression.squared_loss, opt, rc)
    theta0 = jnp.zeros((d,))
    args = (theta0, opt.init(theta0), regression.worker_batches(ds),
            jax.random.PRNGKey(2))
    return run, args


def test_round_runner_is_scoped_and_lowers_what_it_runs():
    run, args = _linreg_runner("reference")
    compiled = run.lower(*args, num_rounds=3).compile()
    assert scope_problems(compiled.as_text(), ROUND_SCOPES,
                          FUSED_SCOPES)[0] == []
    np.testing.assert_array_equal(
        np.asarray(compiled(*args, None, None, 0)[0]),
        np.asarray(run(*args, num_rounds=3)[0]))


def test_fused_round_is_scoped():
    run, args = _linreg_runner("fused_interpret")
    text = run.lower(*args, num_rounds=2).compile().as_text()
    assert scope_problems(text, ("round_kernel", "aggregate"))[0] == []


def _python_weiszfeld_count(reported, *, trim_multiplier, max_iters, tol,
                            eps=1e-12):
    """Weiszfeld steps to ``tol`` in float64 numpy, on the k reports as the
    batch means (k batches of one group each), after the norm trim."""
    leaves = jax.tree.leaves(reported)
    z = np.concatenate([np.asarray(l, np.float64).reshape(l.shape[0], -1)
                        for l in leaves], axis=1)
    norms = np.linalg.norm(z, axis=1)
    w = (norms <= trim_multiplier * np.median(norms) + eps).astype(float)
    y, it, delta = w @ z / w.sum(), 0, np.inf
    while it < max_iters and delta > tol * tol:
        inv = w / np.sqrt(((z - y) ** 2).sum(axis=1) + eps * eps)
        y_new = (inv / inv.sum()) @ z
        y, it, delta = y_new, it + 1, ((y_new - y) ** 2).sum()
    return it


@pytest.mark.parametrize("max_iters", [32, 3])
def test_weiszfeld_iters_counts_the_loop(max_iters):
    # at this size the loop's squared movement falls about 4x a step; tol
    # sits between two of them, well away from either
    tol = 4.3e-4
    cfg, rc, stream = _tiny(gmom_tol=tol, gmom_max_iters=max_iters)
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    batch, key = stream.batch(0), jax.random.PRNGKey(5)
    step = jax.jit(steps.make_group_train_step(cfg, rc, optim.sgd(0.1)))
    _, _, metrics = step(params, optim.sgd(0.1).init(params), batch, key,
                         jnp.int32(0))
    _, grads = jax.jit(steps.make_group_grads(cfg))(params, batch)
    reported, _ = steps.report_groups(grads, rc, key, 0)
    want = _python_weiszfeld_count(reported, trim_multiplier=3.0,
                                   max_iters=max_iters, tol=tol)
    assert metrics["weiszfeld_iters"].dtype == jnp.int32
    assert int(metrics["weiszfeld_iters"]) == want
    assert 1 < want < 32 if max_iters == 32 else want == 3


@pytest.mark.parametrize("aggregator", ["mean", "krum"])
def test_weiszfeld_iters_is_zero_without_the_loop(aggregator):
    cfg, rc, stream = _tiny(aggregator=aggregator)
    gmom_cfg, gmom_rc, _ = _tiny()
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    args = (stream.batch(0), jax.random.PRNGKey(5), jnp.int32(0))
    opt = optim.sgd(0.1)
    _, _, metrics = jax.jit(steps.make_group_train_step(cfg, rc, opt))(
        params, opt.init(params), *args)
    _, _, gmom_metrics = jax.jit(steps.make_group_train_step(
        gmom_cfg, gmom_rc, opt))(params, opt.init(params), *args)
    assert int(metrics["weiszfeld_iters"]) == 0
    assert int(gmom_metrics["weiszfeld_iters"]) > 0
    assert set(metrics) == set(gmom_metrics)
