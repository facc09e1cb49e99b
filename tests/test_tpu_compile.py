"""The fused GMoM round kernel compiles for a TPU v5e.

Interpret mode cannot show what the chip's compiler refuses (layouts it
cannot relayout, scoped VMEM it cannot allocate), so these tests compile
``round_aggregate_kernel`` for a v5e that is described, not attached, at
the paper's m=50, k=11 and at the largest d the dispatcher's ``fits_vmem``
admits.  They also check that the chip's compiler keeps the step's named
scopes, which is how a profile of the step is split by layer.  The
topology is described inside a fixture: only the worker that runs this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import optim
from repro.configs import get_config
from repro.core import RobustConfig
from repro.core.grouping import make_grouping
from repro.data.tokens import TokenStream
from repro.kernels.geomed import round as round_kernel
from repro.launch import steps
from repro.models import model as model_lib
from repro.roofline.hlo_parser import op_names

M, K = 50, 11


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def largest_admitted_d(m: int, k: int) -> int:
    lo, hi = 1, 1 << 24
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if round_kernel.fits_vmem(m, k, mid):
            lo = mid
        else:
            hi = mid - 1
    return lo


@pytest.mark.parametrize("d", [100, 1000, "largest"])
def test_round_kernel_compiles_for_v5e(one_chip, d):
    if d == "largest":
        d = largest_admitted_d(M, K)
        assert not round_kernel.fits_vmem(M, K, d + round_kernel.TILE_D)
    grads = jax.ShapeDtypeStruct((M, d), jnp.float32, sharding=one_chip)
    compiled = round_kernel.round_aggregate_kernel.lower(
        grads, make_grouping(M, K), max_iters=32, tol=1e-7).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_round_keeps_its_name_under_its_scope(one_chip):
    """The benchmark finds the kernel's op by its instruction name,
    ``round_aggregate_kernel.N``; the ``round_kernel`` scope is metadata."""
    grads = jax.ShapeDtypeStruct((M, 1000), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda g: round_kernel.round_aggregate_pytree(
        {"w": g}, make_grouping(M, K), max_iters=32, tol=1e-7,
        use_pallas=True))
    kernels = [(name, path) for name, _, path in
               op_names(fn.lower(grads).compile().as_text())
               if name.split(".")[0] == "round_aggregate_kernel"]
    assert len(kernels) == 1
    assert "round_kernel" in kernels[0][1].split("/")


def test_group_step_scopes_survive_the_v5e_compiler(one_chip):
    """Every instruction the tiny gmom group step's traced operations become
    on a v5e sits under one of its layers' scopes."""
    cfg = get_config("minitron-4b").reduced()
    rc = RobustConfig(num_workers=4, num_byzantine=1, num_batches=2,
                      attack="sign_flip", aggregator="gmom",
                      round_backend="reference")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=8, num_workers=4, seed=0)
    opt = optim.adamw(1e-3)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(lambda k: model_lib.init(k, cfg),
                                    jax.random.PRNGKey(0)))
    opt_state = on_chip(jax.eval_shape(opt.init, params))
    batch = on_chip(jax.eval_shape(stream.batch, 0))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    jitted, _ = steps.jit_group_train_step(cfg, rc, opt, params, opt_state,
                                           batch)
    text = jitted.lower(params, opt_state, batch, key, jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=one_chip)).compile().as_text()
    layers = {"group_fwd_bwd", "attack", "aggregate", "optimizer",
              "step_metrics"}
    rows = op_names(text)
    seen = {p for _, _, path in rows if path for p in path.split("/")}
    assert layers | {"batch_means", "trim", "weiszfeld"} <= seen
    unscoped = [(name, path) for name, _, path in rows
                if path and path.startswith("jit(")
                and not layers & set(path.split("/"))]
    assert unscoped == []
