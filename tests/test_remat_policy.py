"""The blocks' checkpoint policy, and how the group step chooses it.

Under ``cfg.remat`` the step keeps each block's projection and MLP matmul
outputs for the backward (``blocks.SAVE_DOTS``) where the compiled step fits
the device's memory, and recomputes the whole block otherwise.  Which values
are kept must not change what is computed: the loss and every gradient leaf
match full remat and no remat.  The CPU reports no memory limit, so the
limit is handed to the chooser here."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config
from repro.configs.moonlight_16b_a3b import CHIP_SHARE
from repro.core import RobustConfig
from repro.data.tokens import TokenStream
from repro.launch import steps
from repro.models import blocks
from repro.models import model as M

K = 2
POLICIES = {"full": None, "dots": blocks.SAVE_DOTS}


def dense():
    return get_config("h2o-danube-3-4b").reduced().with_(remat=True)


def mla_moe():
    return CHIP_SHARE.reduced().with_(num_layers=3, num_experts=8,
                                      experts_per_token=3, experts_held=2,
                                      experts_held_lo=2, remat=True)


CONFIGS = {"dense": dense, "mla_moe": mla_moe}


def _batch(cfg, seed=0):
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=2 * K, num_workers=K, seed=seed)
    return stream.batch(0)


def _shapes(cfg, opt):
    params = jax.eval_shape(lambda k: M.init(k, cfg), jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: _batch(cfg))
    return params, jax.eval_shape(opt.init, params), batch


def _rc():
    return RobustConfig(num_workers=K, num_byzantine=1, num_batches=K,
                        attack="sign_flip", aggregator="gmom")


def _lowered(cfg, policy, opt):
    step = jax.jit(steps.make_group_train_step(cfg, _rc(), opt,
                                               remat_policy=policy),
                   donate_argnums=(0, 1))
    return step.lower(*_shapes(cfg, opt), jax.random.PRNGKey(0),
                      jnp.int32(0))


def test_loss_and_grads_do_not_depend_on_what_is_kept():
    """The dense stack; the latent-attention and expert stack's case is
    ``test_mla_moe.test_model_loss_and_grads_match_reference[dots]``."""
    cfg = dense()
    params = M.init(jax.random.PRNGKey(1), cfg)
    group = jax.tree.map(lambda x: x[0], _batch(cfg))

    def value_and_grad(cfg, policy):
        with blocks.remat_policy(policy):
            return jax.jit(jax.value_and_grad(M.loss_fn),
                           static_argnums=2)(params, group, cfg)

    want, want_grads = value_and_grad(cfg.with_(remat=False), None)
    for name, policy in POLICIES.items():
        loss, grads = value_and_grad(cfg, policy)
        np.testing.assert_allclose(loss, want, rtol=1e-6, err_msg=name)
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(grads)[0],
                jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(
                g, w, rtol=1e-5, atol=1e-7,
                err_msg=f"{name} {jax.tree_util.keystr(path)}")


def _rematted_dots(hlo_text):
    """``(einsum, has batch dims)`` of each ``dot`` instruction the backward
    recomputes (an ``op_name`` through ``rematted_computation``).  Left out:
    the routed experts' ``ragged_dot``s (scope ``experts``), which the CPU
    lowers to dots and which are no ``dot_general`` to save."""
    out = []
    for line in hlo_text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if (re.search(r"= \S+ dot\(", line) and name
                and "rematted_computation" in name.group(1).split("/")):
            einsum = name.group(1).split("/")[-2]
            if einsum != "experts":
                out.append((einsum, "lhs_batch_dims" in line))
    return out


@pytest.mark.parametrize("config", CONFIGS)
def test_saving_dots_recomputes_no_projection(config):
    """Full remat recomputes the projections and the MLP's matmuls (no
    batch dims); saving the dots recomputes only the attention core's
    score and value products (batch dims over batch and heads)."""
    cfg = CONFIGS[config]()
    opt = optim.sgd(0.1)
    full, dots = (_rematted_dots(_lowered(cfg, p, opt).as_text(
        dialect="hlo", debug_info=True)) for p in POLICIES.values())
    projections = {e for e, batched in full if not batched}
    assert {"btd,dhk->bthk", "...d,df->...f"} <= projections
    assert [e for e, batched in dots if not batched] == []
    assert dots and {e for e, _ in dots} == {e for e, b in full if b}


def test_chooser_keeps_dots_only_under_the_limit():
    cfg = dense()
    compiled = _lowered(cfg, blocks.SAVE_DOTS, optim.sgd(0.1)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak > 0
    calls = []

    def compile_saving_dots():
        calls.append(1)
        return compiled

    choose = steps.choose_remat_policy
    assert choose(compile_saving_dots, None) is None and not calls
    assert choose(compile_saving_dots, peak // 2) is None
    # the margin: a limit the peak just reaches is too little
    assert choose(compile_saving_dots, peak) is None
    assert choose(compile_saving_dots, 2 * peak) is blocks.SAVE_DOTS

    def out_of_memory():
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")
    assert choose(out_of_memory, 2 * peak) is None

    def broken():
        raise jax.errors.JaxRuntimeError("INTERNAL: something else")
    with pytest.raises(jax.errors.JaxRuntimeError):
        choose(broken, 2 * peak)


@pytest.mark.parametrize("limit,policy", [(None, "full"), (1, "full"),
                                          (1 << 40, "dots")])
def test_jitted_step_is_the_chosen_program(monkeypatch, limit, policy):
    """``jit_group_train_step`` returns the full-remat program where the
    device reports no limit (the CPU, a dry run) or the dots do not fit,
    and the dots program where they do; a caller compiling the returned
    step for a key and a round gets the chooser's compile back."""
    cfg = dense()
    opt = optim.adamw(1e-3)
    shapes = _shapes(cfg, opt)
    monkeypatch.setattr(steps, "device_bytes_limit", lambda device: limit)
    jitted, _ = steps.jit_group_train_step(cfg, _rc(), opt, *shapes)
    key, rnd = jax.random.PRNGKey(3), jnp.int32(0)
    compiles = []

    def listen(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        lowered = jitted.lower(*shapes, key, rnd)
        lowered.compile()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert lowered.as_text() == _lowered(cfg, POLICIES[policy],
                                         opt).as_text()
    assert len(compiles) == (0 if policy == "dots" else 1)


def test_no_remat_is_never_probed(monkeypatch):
    cfg = dense().with_(remat=False)
    opt = optim.sgd(0.1)

    def unread(device):
        raise AssertionError("a step without remat has no policy to choose")

    monkeypatch.setattr(steps, "device_bytes_limit", unread)
    jitted, _ = steps.jit_group_train_step(cfg, _rc(), opt,
                                           *_shapes(cfg, opt))
    assert jitted.lower(*_shapes(cfg, opt), jax.random.PRNGKey(0),
                        jnp.int32(0)).as_text() == \
        _lowered(cfg, None, opt).as_text()
