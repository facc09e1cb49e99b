"""The fused GMoM round kernel compiles for a TPU v5e.

Interpret mode cannot show what the chip's compiler refuses (layouts it
cannot relayout, scoped VMEM it cannot allocate), so these tests compile
``round_aggregate_kernel`` for a v5e that is described, not attached, at
the paper's m=50, k=11 and at the largest d the dispatcher's ``fits_vmem``
admits.  The topology is described inside a fixture: only the worker that
runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.grouping import make_grouping
from repro.kernels.geomed import round as round_kernel

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
