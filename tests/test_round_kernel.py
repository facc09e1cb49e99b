"""Fused Pallas round kernel (kernels/geomed/round.py) validation.

Three layers of guarantees:

(a) bit-equality — the kernel in interpret mode and its tile-mirroring jnp
    reference produce EXACTLY the same bytes for every grouping scheme and
    every (m, k, d) in the tier-1 matrix, including the uneven paper-scale
    m=50, k=11 partition (this is the acceptance bar for the fused lowering:
    no silent numerical drift between backends' formulations);
(b) semantics — the fused path agrees with the unfused jnp gmom pipeline
    (batch means -> Remark-2 trim -> pytree Weiszfeld) to float tolerance,
    for flat and nested gradient pytrees;
(c) system — a checked-in golden scenario trace replayed with
    round_backend="fused_interpret" reproduces the recorded trajectory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators
from repro.core.grouping import assignment_matrix, make_grouping
from repro.kernels.geomed import round as round_kernel

# the tier-1 (m, k, d) matrix: even + uneven (paper-scale m=50, k=11),
# single-tile + multi-tile + unaligned d.
MKD_MATRIX = [
    (12, 6, 64),
    (20, 10, 1000),
    (50, 11, 777),        # uneven: the paper's experimental geometry
    (8, 4, 2048),
    pytest.param((64, 16, 4096), marks=pytest.mark.slow),
]
SCHEMES = ("contiguous", "strided", "seeded")


def _stacked(m, d, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(m, d)).astype(np.float32) + 1.0)


# ---------------------------------------------------------------------------
# (a) bit-equality: kernel (interpret) vs jnp reference

@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("mkd", MKD_MATRIX)
def test_round_kernel_bit_identical_to_ref(mkd, scheme):
    m, k, d = mkd
    g = _stacked(m, d, seed=m * d)
    grouping = make_grouping(m, k, scheme=scheme)
    ker = round_kernel.round_aggregate_kernel(g, grouping, interpret=True,
                                              max_iters=16)
    ref = round_kernel.round_aggregate_ref(g, grouping, max_iters=16)
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))


@pytest.mark.parametrize("trim", [None, 1.0, 3.0])
def test_round_kernel_bit_identical_across_trim(trim):
    g = _stacked(16, 700, seed=7)
    # one huge outlier row so trim=1.0 actually zeroes a batch
    g = g.at[0].mul(100.0)
    grouping = make_grouping(16, 8)
    ker = round_kernel.round_aggregate_kernel(
        g, grouping, interpret=True, trim_multiplier=trim, max_iters=16)
    ref = round_kernel.round_aggregate_ref(
        g, grouping, trim_multiplier=trim, max_iters=16)
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))


def test_median_small_matches_jnp_median():
    rng = np.random.default_rng(11)
    for k in (2, 3, 8, 11, 16):
        x = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
        np.testing.assert_allclose(
            float(round_kernel._median_small(x[:, None])),
            float(jnp.median(x)), rtol=1e-6)
        # ties must not break the rank-selection
        x_t = jnp.concatenate([x[: k // 2], x[: k - k // 2]])
        np.testing.assert_allclose(
            float(round_kernel._median_small(x_t[:, None])),
            float(jnp.median(x_t)), rtol=1e-6)


def test_round_kernel_rejects_over_budget_blocks():
    g = _stacked(4, 128)
    grouping = make_grouping(4, 2)
    with pytest.raises(ValueError, match="VMEM budget"):
        round_kernel._check_vmem(64, 256 * round_kernel.TILE_D)
    del g, grouping


# ---------------------------------------------------------------------------
# (b) semantics: fused vs the unfused jnp gmom pipeline

@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("mkd", [(12, 6, 64), (20, 10, 1000), (50, 11, 777)])
def test_fused_gmom_matches_reference_flat(mkd, scheme):
    """Semantic agreement between the two independent pipelines (fused
    kernel vs pre-existing jnp reference) for EVERY grouping scheme — this
    is the non-circular check that the membership matrix and the
    reference's permute/reshape agree on the partition."""
    m, k, d = mkd
    g = _stacked(m, d, seed=1)
    ref = aggregators.gmom_aggregator(g, num_batches=k,
                                      grouping_scheme=scheme,
                                      round_backend="reference")
    fus = aggregators.gmom_aggregator(g, num_batches=k,
                                      grouping_scheme=scheme,
                                      round_backend="fused_interpret")
    assert fus.shape == ref.shape and fus.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(fus), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_strided_batches_are_residue_classes():
    """Ground truth for the partition itself, independent of any
    aggregation code: the strided scheme puts worker w in batch w % k."""
    m, k = 12, 4
    grouping = make_grouping(m, k, scheme="strided")
    assert grouping.batches() == [[w for w in range(m) if w % k == l]
                                  for l in range(k)]
    s = assignment_matrix(grouping)
    for l in range(k):
        assert set(np.nonzero(s[l])[0]) == {w for w in range(m)
                                            if w % k == l}


def test_fused_gmom_matches_reference_pytree():
    rng = np.random.default_rng(2)
    s = {"w": jnp.asarray(rng.normal(size=(12, 5)).astype(np.float32) + 1.0),
         "b": {"x": jnp.asarray(
             rng.normal(size=(12, 2, 3)).astype(np.float32) + 1.0)}}
    ref = aggregators.gmom_aggregator(s, num_batches=6,
                                      round_backend="reference")
    fus = aggregators.gmom_aggregator(s, num_batches=6,
                                      round_backend="fused_interpret")
    assert jax.tree.structure(fus) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(fus), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_uneven_batch_means_are_group_means():
    """The membership-matmul path (k does not divide m) computes exactly the
    per-group means of the permuted workers."""
    m, k = 10, 3
    g = _stacked(m, 7, seed=9)
    grouping = make_grouping(m, k, scheme="strided")
    means = aggregators.batch_means(g, k, scheme="strided")
    assert means.shape == (k, 7)
    s = assignment_matrix(grouping)
    for l, members in enumerate(grouping.batches()):
        assert sorted(np.nonzero(s[l])[0].tolist()) == sorted(members)
        np.testing.assert_allclose(
            np.asarray(means[l]),
            np.mean(np.asarray(g)[members], axis=0), rtol=1e-6)


def test_choose_num_batches_uneven_opt_in():
    """Default (prefer_even) keeps the historical divisor-based canonical k
    (golden-trace stable); prefer_even=False reaches the paper's exact
    experimental geometry m=50, q=5 -> k=11."""
    from repro.core.grouping import choose_num_batches
    assert choose_num_batches(50, 5) == 25                      # divisor
    assert choose_num_batches(50, 5, prefer_even=False) == 11   # paper
    assert choose_num_batches(20, 0) == 1


def test_resolve_round_backend():
    resolve = aggregators.resolve_round_backend
    # explicit values pass through regardless of backend
    for b in ("reference", "fused", "fused_interpret"):
        assert resolve(b, num_batches=8) == b
    with pytest.raises(ValueError, match="round_backend"):
        resolve("nope", num_batches=8)
    # auto on a non-TPU host (this CI) resolves to the reference path
    if jax.default_backend() != "tpu":
        assert resolve("auto", num_batches=8, total_dim=1000) == "reference"
        assert resolve(None, num_batches=8) == "reference"


@pytest.mark.parametrize("shape,partitioned,expected", [
    ((50, 11, 1000), False, "fused"),                 # linreg.paper.gmom
    ((4, 4, 468_996_096), False, "reference"),        # mistral7b.gmom cell
    ((50, 11, 1000), True, "reference"),              # param-sharded slices
], ids=["linreg_paper", "mistral_cell", "partitioned"])
def test_resolve_round_backend_auto_for_tpu(shape, partitioned, expected):
    """What ``auto`` picks for a program that runs on a TPU, whatever the
    host: the paper's round fits the kernel's VMEM budget, a model-scale
    gradient does not, and partitioned gradients never take the kernel."""
    m, k, d = shape
    assert aggregators.resolve_round_backend(
        "auto", num_batches=k, total_dim=d, num_workers=m,
        target_backend="tpu", partitioned=partitioned) == expected


# ---------------------------------------------------------------------------
# (c) system: golden-trace replay through the fused path

def test_golden_replay_through_fused_path():
    """One checked-in golden scenario, re-run with the gmom hot path
    dispatched through the Pallas round kernel (interpret mode), reproduces
    the recorded trajectory.  Tolerance: the fused formulation computes in
    f32 with a different (but fixed) reduction order, so traces agree to
    float precision rather than byte-for-byte."""
    from repro import sim
    from repro.sim import goldens
    name = "linreg/gmom/sign_flip/rotating"
    trace = sim.run_scenario(name, round_backend="fused_interpret")
    gold = goldens.load_golden(name)
    np.testing.assert_allclose(np.array(trace["est_error"]),
                               np.array(gold["est_error"]),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(trace["final_est_error"],
                               gold["final_est_error"], rtol=1e-3)
    assert trace["byz_count"] == gold["byz_count"]
