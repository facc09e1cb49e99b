"""Property tests for the geometric median (paper §2.1, Lemma 1).

``hypothesis`` is optional: when installed the properties run under its
strategies; otherwise the same checks run over a parametrized set of
deterministic seeds so the core properties are always exercised (the tier-1
environment does not ship hypothesis).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geometric_median, geometric_median_pytree, \
    trim_weights, batch_mean_norms
from repro.core.geometric_median import GRAM_PAIRS_MAX_K
from repro.core.theory import c_alpha

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

FALLBACK_SEEDS = list(range(5))


def _random_points(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 13))
    d = int(rng.integers(1, 7))
    return (rng.normal(size=(n, d)) * 10).astype(np.float32)


def property_test(*, needs_shift=False, needs_seed=False):
    """Run the check under hypothesis when available, else over seeds.

    The wrapped check takes ``pts`` (and optionally ``shift``/``seed``).
    """
    def deco(check):
        if HAVE_HYPOTHESIS:
            if needs_shift:
                return given(points_strategy,
                             st.lists(st.floats(-50, 50, allow_nan=False,
                                                width=32),
                                      min_size=6, max_size=6))(check)
            if needs_seed:
                return given(points_strategy,
                             st.integers(0, 2**31 - 1))(check)
            return given(points_strategy)(check)

        @pytest.mark.parametrize("seed", FALLBACK_SEEDS)
        def fallback(seed):
            pts = _random_points(seed)
            rng = np.random.default_rng(seed + 1000)
            if needs_shift:
                check(pts, list(rng.uniform(-50, 50, size=6)))
            elif needs_seed:
                check(pts, int(rng.integers(0, 2**31 - 1)))
            else:
                check(pts)
        fallback.__name__ = check.__name__
        fallback.__doc__ = check.__doc__
        return fallback
    return deco


if HAVE_HYPOTHESIS:
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")
    points_strategy = st.builds(
        lambda seed, n, d: np.random.default_rng(seed)
        .normal(size=(n, d)).astype(np.float32) * 10,
        st.integers(0, 2**31 - 1), st.integers(2, 12), st.integers(1, 6))


@property_test()
def test_objective_not_worse_than_mean(pts):
    """geomed minimizes sum of distances => objective <= mean's objective."""
    gm = geometric_median(jnp.asarray(pts), max_iters=128, tol=1e-10)
    mean = pts.mean(axis=0)

    def obj(y):
        return float(np.sum(np.linalg.norm(pts - y, axis=1)))

    assert obj(np.asarray(gm)) <= obj(mean) + 1e-3 * (1 + abs(obj(mean)))


@property_test(needs_shift=True)
def test_translation_equivariance(pts, shift):
    shift = np.array(shift[:pts.shape[1]], np.float32)
    g1 = np.asarray(geometric_median(jnp.asarray(pts), max_iters=96))
    g2 = np.asarray(geometric_median(jnp.asarray(pts + shift), max_iters=96))
    np.testing.assert_allclose(g1 + shift, g2, atol=2e-2)


@property_test(needs_seed=True)
def test_permutation_invariance(pts, seed):
    perm = np.random.default_rng(seed).permutation(pts.shape[0])
    g1 = np.asarray(geometric_median(jnp.asarray(pts)))
    g2 = np.asarray(geometric_median(jnp.asarray(pts[perm])))
    np.testing.assert_allclose(g1, g2, atol=1e-3)


@property_test()
def test_within_bounding_box(pts):
    """geomed lies in the convex hull => inside the bounding box."""
    g = np.asarray(geometric_median(jnp.asarray(pts), max_iters=128))
    lo, hi = pts.min(axis=0), pts.max(axis=0)
    assert np.all(g >= lo - 1e-2) and np.all(g <= hi + 1e-2)


def test_single_point_and_mean_reduction():
    pts = jnp.array([[3.0, -2.0, 5.0]])
    np.testing.assert_allclose(np.asarray(geometric_median(pts)),
                               [3.0, -2.0, 5.0], atol=1e-6)


def test_lemma1_robustness():
    """Lemma 1 (gamma=0): if > (1-alpha) n points lie in B(0, r), then
    ||geomed|| <= C_alpha r."""
    rng = np.random.default_rng(0)
    n, d, alpha, r = 20, 8, 0.25, 1.0
    n_in = int((1 - alpha) * n) + 1
    inliers = rng.normal(size=(n_in, d))
    inliers = inliers / np.linalg.norm(inliers, axis=1, keepdims=True) \
        * rng.uniform(0, r, (n_in, 1))
    outliers = rng.normal(size=(n - n_in, d)) * 1e4
    pts = jnp.asarray(np.vstack([inliers, outliers]), jnp.float32)
    g = geometric_median(pts, max_iters=256, tol=1e-10)
    assert float(jnp.linalg.norm(g)) <= c_alpha(alpha) * r + 1e-3


def test_median_1d_matches_numpy_median_interval():
    """In 1-D the geometric median is a median."""
    pts = jnp.array([[1.0], [2.0], [3.0], [10.0], [11.0]])
    g = float(geometric_median(pts, max_iters=512, tol=1e-12)[0])
    assert 2.9 <= g <= 3.1


def test_pytree_matches_flat():
    rng = np.random.default_rng(1)
    pts = rng.normal(size=(7, 10)).astype(np.float32)
    flat = geometric_median(jnp.asarray(pts), max_iters=128)
    tree = {"a": jnp.asarray(pts[:, :4]),
            "b": {"c": jnp.asarray(pts[:, 4:])}}
    gt = geometric_median_pytree(tree, max_iters=128)
    merged = np.concatenate([np.asarray(gt["a"]),
                             np.asarray(gt["b"]["c"])])
    np.testing.assert_allclose(np.asarray(flat), merged, atol=1e-4)


def test_weights_zero_excludes_points():
    pts = jnp.array([[0.0, 0.0], [0.1, 0.0], [-0.1, 0.0], [1e6, 1e6]])
    w = jnp.array([1.0, 1.0, 1.0, 0.0])
    g = geometric_median(pts, weights=w, max_iters=256)
    assert float(jnp.linalg.norm(g)) < 0.2


def test_trim_weights():
    norms = jnp.array([1.0, 1.1, 0.9, 1.05, 500.0])
    w = trim_weights(norms, multiplier=3.0)
    np.testing.assert_array_equal(np.asarray(w), [1, 1, 1, 1, 0])
    # never all-zero
    w2 = trim_weights(jnp.array([1e9, 1e9]), multiplier=0.0)
    assert float(jnp.sum(w2)) > 0


def test_batch_mean_norms():
    tree = {"a": jnp.array([[3.0, 0.0], [0.0, 0.0]]),
            "b": jnp.array([[4.0], [0.0]])}
    norms = batch_mean_norms(tree)
    np.testing.assert_allclose(np.asarray(norms), [5.0, 0.0], atol=1e-6)


def test_jit_and_grad_safe():
    pts = jnp.asarray(np.random.default_rng(2).normal(size=(6, 4)),
                      jnp.float32)
    g = jax.jit(lambda p: geometric_median(p))(pts)
    assert g.shape == (4,)
    assert bool(jnp.all(jnp.isfinite(g)))


def _float64_weiszfeld(z, w, *, max_iters, tol, eps=1e-12):
    """The point-form Weiszfeld in float64 numpy on (k, d) points, with the
    pytree form's start, smoothing and stopping rule."""
    y, it, delta = w @ z / max(w.sum(), eps), 0, np.inf
    while it < max_iters and delta > tol * tol:
        inv = w / np.sqrt(((z - y) ** 2).sum(axis=1) + eps * eps)
        y_new = (inv / max(inv.sum(), eps)) @ z
        y, it, delta = y_new, it + 1, ((y_new - y) ** 2).sum()
    return y


def _gram_case(name):
    """``(leaves, weights or None, tolerance)`` of one case: the tolerance
    on the largest coordinate gap, as a share of the largest coordinate
    (f32 rounding, more where the distances cancel; bf16 leaves come back
    in bf16, formed by a bf16 multiply-add chain)."""
    rng = np.random.default_rng(7)
    if name == "random":
        z = rng.normal(size=(7, 10)) * 10
        return [z[:, :4], z[:, 4:].reshape(7, 3, 2)], None, 1e-6
    if name == "trimmed_sign_flip":
        z = rng.normal(size=(5, 12)) + 3.0
        z[4] = -10.0 * z[0]
        return [z[:, :5], z[:, 5:]], "trim", 1e-6
    if name == "near_coincident":
        # three reports 1e-4 apart around a far offset: the median lies on
        # top of them, where G_ii - 2(Gc)_i + cᵀGc cancels
        base = rng.normal(size=12) * 100
        z = base + rng.normal(size=(5, 12))
        z[:3] = base + 1e-4 * rng.normal(size=(3, 12))
        return [z[:, :7], z[:, 7:]], None, 1e-5
    if name == "bf16_leaves":
        z = rng.normal(size=(6, 3 * 5 + 7 + 8)) + 1.0
        return ([z[:, :15].reshape(6, 3, 5), z[:, 15:22],
                 z[:, 22:].reshape(6, 2, 2, 2)], "bf16", 2 ** -7)
    if name == "k1":
        return [rng.normal(size=(1, 9))], None, 1e-6
    if name == "k2":
        z = rng.normal(size=(2, 9))
        return [z[:, :4], z[:, 4:]], None, 1e-6
    # past GRAM_PAIRS_MAX_K the Gram matrix is one dot_general
    if name == "k9":
        z = rng.normal(size=(9, 21)) * 10
        return [z[:, :6], z[:, 6:].reshape(9, 5, 3)], None, 1e-6
    if name == "k16":
        z = rng.normal(size=(16, 40)) * 10
        return [z[:, :16], z[:, 16:].reshape(16, 4, 6)], None, 1e-6
    if name == "k16_trimmed_sign_flip":
        z = rng.normal(size=(16, 24)) + 3.0
        z[15] = -10.0 * z[0]
        return [z[:, :10], z[:, 10:]], "trim", 1e-6
    if name == "k16_bf16_leaves":
        z = rng.normal(size=(16, 3 * 5 + 7 + 8)) + 1.0
        return ([z[:, :15].reshape(16, 3, 5), z[:, 15:22],
                 z[:, 22:].reshape(16, 2, 2, 2)], "bf16", 2 ** -7)
    raise ValueError(name)


@pytest.mark.parametrize("case", ["random", "trimmed_sign_flip",
                                  "near_coincident", "bf16_leaves", "k1",
                                  "k2", "k9", "k16", "k16_trimmed_sign_flip",
                                  "k16_bf16_leaves"])
def test_gram_form_matches_point_form(case):
    """The pytree Weiszfeld, iterating on coefficients over X Xᵀ, lands on
    the point form's median: the f32 flat ``geometric_median`` and a
    float64 numpy Weiszfeld with the same start and stopping rule."""
    leaves, weights, tol = _gram_case(case)
    dtype = jnp.bfloat16 if weights == "bf16" else jnp.float32
    tree = [jnp.asarray(l, dtype) for l in leaves]
    k = leaves[0].shape[0]
    z = np.concatenate([np.asarray(l, np.float64).reshape(k, -1)
                        for l in tree], axis=1)
    w = None
    if weights == "trim":
        w = trim_weights(batch_mean_norms(tree), multiplier=3.0)
        assert float(w[-1]) == 0.0 and float(jnp.sum(w)) == k - 1
    # stop where f32 coefficients stop moving the point: a millionth of
    # the largest report's norm
    move = 1e-6 * float(np.max(np.linalg.norm(z, axis=1)))
    info = {}
    got = geometric_median_pytree(tree, weights=w, max_iters=200, tol=move,
                                  info=info)
    # the Gram matrix's form: fused pair reductions up to
    # GRAM_PAIRS_MAX_K, one dot_general past it
    jaxpr = str(jax.make_jaxpr(lambda t: geometric_median_pytree(
        t, weights=w, max_iters=200, tol=move))(tree))
    assert ("dot_general" in jaxpr) == (k > GRAM_PAIRS_MAX_K)
    assert [g.dtype for g in got] == [dtype] * len(tree)
    got = np.concatenate([np.asarray(g, np.float64).reshape(-1)
                          for g in got])
    w64 = np.ones(k) if w is None else np.asarray(w, np.float64)
    want64 = _float64_weiszfeld(z, w64, max_iters=200, tol=move)
    flat = np.asarray(geometric_median(jnp.asarray(z, jnp.float32),
                                       weights=w, max_iters=200, tol=move),
                      np.float64)
    scale = np.max(np.abs(z))
    assert np.max(np.abs(got - want64)) <= tol * scale
    assert np.max(np.abs(got - flat)) <= tol * scale
    assert 1 <= int(info["weiszfeld_iters"]) < 200
