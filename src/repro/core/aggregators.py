"""Robust gradient aggregation rules.

Every aggregator maps a *stacked* per-worker gradient pytree (leaves with a
leading worker axis ``m``) to a single gradient pytree (no leading axis).
All are pure jnp/lax so they jit and shard (the worker axis is sharded over
the mesh ``data`` axis; param dims over ``model`` — reductions become psums).

The paper's contribution is ``gmom`` (geometric median of means, Algorithm 2);
``mean`` is the paper's Algorithm 1 baseline (classical BGD).  The rest are
well-known robust baselines used for the comparison benchmarks:

* ``geomed``            — k = m special case (paper §2.1)
* ``trimmed_mean``      — coordinate-wise beta-trimmed mean [Yin et al. '18]
* ``coordinate_median`` — coordinate-wise median
* ``krum``              — Blanchard et al. '17 [BMGS17], the paper's closest
                          related work; selects the worker whose gradient has
                          the smallest sum of distances to its m-q-2 closest.
* ``norm_clip_mean``    — mean of norm-clipped gradients (practical baseline)

The naive paper-§6 selection rules (``random_select``, ``norm_select``,
and the ``norm_clip_mean`` baseline) are KNOWN-UNSOUND under the adaptive
small-norm attacks; the **sound combined selection rules** close that gap
(see the section comment above their definitions):

* ``coord_median``       — coordinate-wise median of the k batch means
                           [Yin et al. '18]
* ``coord_trimmed_mean`` — coordinate-wise q-trimmed mean of the k batch
                           means [Yin et al. '18]
* ``norm_filter_gmom``   — two-sided norm-envelope filter (median ± c·MAD,
                           dropping huge AND adversarially-small outliers)
                           then GMoM on the survivors [Su & Xu '18]

The **communication-compressed rules** consume the wire formats of
``repro.core.compression`` natively (see their section comment):

* ``sign_sgd_majority``  — coordinate-wise majority vote over 1-bit sign
                           gradients [Jin et al. '19] — votes on the packed
                           uint8 wire directly
* ``int8_gmom``          — dequantize-then-GMoM on the 8-bit stochastic
                           wire (per-worker scales), reusing the full gmom
                           pipeline incl. ``round_backend`` dispatch

Every rule honors the **shard-local contract** (see
``repro.core.shard_aggregation``): coordinate-wise rules touch each
parameter shard independently (no cross-shard collectives at all), and the
norm-based rules take an optional ``shard_spec`` so their distance/norm
reductions combine per-shard partials — the (k,) trim norms and one
(k, k) partial Gram matrix per geometric median for GMoM (the Weiszfeld
loop then runs on it with no collective), one (m, m) partial distance
reduction for krum.  A partitioned spec also forces the ``reference``
round backend (the fused kernel's leaf concatenation would gather).

Every ``register(...)`` call carries a one-line description plus the
kwarg-dispatch flags (``needs_num_byzantine`` / ``needs_key`` /
``needs_grouping`` / ``needs_shard_spec``) that
``robust_train.aggregate_reported`` reads;
``describe()`` renders the registry as a markdown table (the one in
README.md), and ``scripts/check_docs.py`` fails CI when a registered name
is missing from ``docs/PAPER_MAP.md`` or has an empty description.

``gmom`` dispatches its hot path through ``round_backend``:

* ``"reference"``       — the original jnp pipeline (batch means -> Remark-2
                          trim -> pytree Weiszfeld).  Bit-stable: the golden
                          scenario traces are recorded on this path.
* ``"fused"``           — the Pallas round kernel
                          (``repro.kernels.geomed.round``): one HBM read of
                          the stacked gradients; means, trimming, and the
                          whole Weiszfeld loop stay VMEM-resident.
* ``"fused_interpret"`` — the same kernel in interpret mode (CPU tests).
* ``"auto"`` (default)  — ``fused`` on TPU backends, ``reference`` elsewhere;
                          also falls back to ``reference`` when the (k, d)
                          block exceeds the kernel's VMEM budget.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


from repro.core.geometric_median import (
    batch_mean_norms, geometric_median, geometric_median_pytree, trim_weights)
from repro.core.grouping import Grouping, make_grouping

# repro: robust-stat — reductions feeding the robust statistics below must
# accumulate in f32 before casting back (checked by repro.verify RV105).

AggregatorFn = Callable[..., object]   # stacked pytree -> pytree

_REGISTRY: dict[str, "Aggregator"] = {}

# The shard-local contract classes (see repro.core.shard_aggregation and
# docs/STATIC_ANALYSIS.md).  Every registered rule declares one; the Layer-B
# contract analyzer (repro.verify.contracts) traces the rule under a
# partitioned ShardSpec and statically verifies the lowered computation:
#
# * "coordinate_wise"  — touches each parameter shard independently: the
#                        lowered IR must contain ZERO cross-shard collectives;
# * "norm_based"       — may combine per-shard partials through small,
#                        d-independent reductions only ((k,)/(m,)/(m,m)
#                        shaped — the O(k)/O(m²) server-cost shape of
#                        PAPER.md §Thm 3);
# * "whole_gradient"   — selects a received gradient verbatim (krum): same
#                        collective allowance as norm_based (the (m,m)
#                        partial gram), selection itself is shard-local.
SHARD_CONTRACTS = ("coordinate_wise", "norm_based", "whole_gradient")

# The bounded-influence op families the Layer-C taint analysis
# (repro.verify.taint / docs/STATIC_ANALYSIS.md) recognizes on a
# report→output dataflow.  A rule that claims robustness declares WHICH
# family sanitizes the reports (its ``sanitization_point``); rules with no
# bounded path (the KNOWN-UNSOUND set) declare ``None``.  The analysis
# never reads the declaration while classifying — it rediscovers the
# family from the traced jaxpr and then *compares* (RV303), so a stale or
# aspirational declaration is itself a finding.
SANITIZATION_POINTS = ("clip", "order_stat", "rank_select", "sign_vote",
                       "weiszfeld")


@dataclasses.dataclass(frozen=True)
class Aggregator:
    """Registry entry: the aggregation fn plus the kwarg-dispatch metadata
    ``robust_train.aggregate_reported`` reads.  The flags replace the old
    hardcoded aggregator-name lists: a newly registered rule declares what
    it consumes and the engine threads it — no dispatch-site edits.

    * ``needs_num_byzantine`` — receives ``num_byzantine=cfg.num_byzantine``.
    * ``needs_key``           — receives a per-round PRNG ``key`` (randomized
                                rules; the paper's omniscient adversary sees
                                the same key).
    * ``needs_grouping``      — receives the full batching/median bundle:
                                ``num_batches``, ``epsilon``,
                                ``grouping_scheme``, ``trim_multiplier``,
                                ``max_iters``/``tol``, and ``round_backend``
                                (rules that don't consume a field swallow it
                                via ``**_kw``).
    * ``needs_shard_spec``    — receives the ``ShardSpec`` describing how
                                the stacked gradients are partitioned over
                                param shards (norm-based rules whose
                                reductions cross shards; coordinate-wise
                                rules are shard-local without one).

    ``shard_contract`` declares which collective footprint the rule is
    allowed to lower to under a partitioned ShardSpec (one of
    ``SHARD_CONTRACTS``); the Layer-B analyzer (``repro.verify.contracts``)
    traces the registered fn and rejects the registration when the lowered
    IR exceeds the declared class.  The default is ``"coordinate_wise"`` —
    deliberately the *strictest* class (zero collectives), so an
    undeclared contract can only ever fail the analyzer loudly, never
    silently grant a rule more communication than it admits to.

    ``native_codec`` names the wire format (``repro.core.compression``)
    the rule consumes directly: when ``RobustConfig.compression`` matches
    it, ``aggregate_reported`` skips the server-side decode and hands the
    rule the encoded payload plus a ``like=`` shape/dtype template
    (``sign_sgd_majority`` votes on packed sign bits; ``int8_gmom``
    dequantizes in-rule).  ``None`` means the rule only ever sees float
    gradients — any configured codec is decoded before dispatch.

    ``sanitization_point`` names the bounded-influence op family (one of
    ``SANITIZATION_POINTS``) through which every worker report must pass
    before reaching the rule's output — the channel PAPER.md §1.3 / Thm 3
    requires to be the ONLY one.  ``None`` = the rule admits unbounded
    per-worker influence (the KNOWN-UNSOUND set).  The Layer-C taint
    analysis (``repro.verify.taint``) verifies the declaration against the
    traced dataflow: RV301 fires when a raw report bypasses it, RV303
    when the declared family does not match the discovered one.
    """
    name: str
    fn: AggregatorFn
    description: str = ""
    needs_num_byzantine: bool = False
    needs_key: bool = False
    needs_grouping: bool = False
    needs_shard_spec: bool = False
    shard_contract: str = "coordinate_wise"
    native_codec: str | None = None
    sanitization_point: str | None = None

    def __call__(self, stacked_grads, **kw):
        return self.fn(stacked_grads, **kw)


def register(name: str, description: str = "", *,
             needs_num_byzantine: bool = False, needs_key: bool = False,
             needs_grouping: bool = False, needs_shard_spec: bool = False,
             shard_contract: str = "coordinate_wise",
             native_codec: str | None = None,
             sanitization_point: str | None = None):
    if shard_contract not in SHARD_CONTRACTS:
        raise ValueError(
            f"aggregator {name!r} declares unknown shard_contract "
            f"{shard_contract!r}; must be one of {SHARD_CONTRACTS}")
    if sanitization_point is not None and \
            sanitization_point not in SANITIZATION_POINTS:
        raise ValueError(
            f"aggregator {name!r} declares unknown sanitization_point "
            f"{sanitization_point!r}; must be None or one of "
            f"{SANITIZATION_POINTS}")
    def deco(fn):
        _REGISTRY[name] = Aggregator(
            name=name, fn=fn, description=description,
            needs_num_byzantine=needs_num_byzantine, needs_key=needs_key,
            needs_grouping=needs_grouping, needs_shard_spec=needs_shard_spec,
            shard_contract=shard_contract, native_codec=native_codec,
            sanitization_point=sanitization_point)
        return fn
    return deco


def get_aggregator(name: str) -> Aggregator:
    if name not in _REGISTRY:
        raise KeyError(f"unknown aggregator {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available() -> list[str]:
    return sorted(_REGISTRY)


def describe() -> list[tuple[str, str]]:
    """(name, description) rows for every registered aggregator, sorted."""
    return [(n, _REGISTRY[n].description) for n in available()]


def describe_markdown() -> str:
    """The registry as a markdown table — the source of the README table
    (kept honest by scripts/check_docs.py)."""
    rows = ["| aggregator | description |", "|---|---|"]
    rows += [f"| `{n}` | {d} |" for n, d in describe()]
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# helpers

def _num_workers(stacked) -> int:
    return jax.tree.leaves(stacked)[0].shape[0]


def bottom_k_mask(scores: jax.Array, k: int) -> jax.Array:
    """{0,1} float mask selecting exactly the k smallest-score entries.

    ``scores <= kth-smallest`` over-selects when values tie (e.g. colluding
    byzantine workers reporting identical gradients, or unlucky uniform
    draws); ranking via stable argsort breaks ties by index so exactly k
    entries are ever selected.
    """
    rank = jnp.argsort(jnp.argsort(scores))
    return (rank < k).astype(jnp.float32)


def _apply_grouping(stacked, grouping: Grouping):
    """Permute + reshape worker axis m -> (k, b) and mean over b.

    Both paths accumulate in f32 and cast back to the leaf dtype, so bf16
    batch means agree between k | m and k ∤ m groupings (beyond permutation
    effects) — previously the even path meant directly in the leaf dtype
    and diverged from the uneven f32 contraction.  Both paths are also
    shard-local: the reduction runs over the worker axis only, per
    coordinate, so partitioned gradient slices need no collectives here."""
    k = grouping.num_batches
    if k == grouping.num_workers and \
            grouping.perm == tuple(range(grouping.num_workers)):
        # identity grouping (k = m, contiguous): every report is its own
        # batch mean.  The group-mode production step lands here (its k
        # batch-group gradients ARE the means), so skip the no-op
        # gather/reshape/mean — a singleton-axis mean is bitwise the
        # identity, but lowers as avoidable data movement on sharded grads.
        return stacked
    if not grouping.is_even:
        batches = grouping.batches()
        sizes = grouping.batch_sizes

        def leaf_uneven(g):
            # members summed one by one in a fixed order: elementwise over
            # the worker axis only, so a sharded trailing dim stays sharded
            # and every shard adds in the same order (a dot over m may
            # block its sum differently for different slice shapes).
            g32 = g.astype(jnp.float32)
            rows = []
            for members, size in zip(batches, sizes):
                acc = g32[members[0]]
                for w in members[1:]:
                    acc = acc + g32[w]
                rows.append(acc / size)
            return jnp.stack(rows).astype(g.dtype)

        return jax.tree.map(leaf_uneven, stacked)

    perm = jnp.asarray(grouping.perm)
    b = grouping.batch_size

    def leaf(g):
        dt = g.dtype
        g = jnp.take(g, jnp.argsort(perm), axis=0)  # order workers by slot
        g = g.reshape((k, b) + g.shape[1:])
        return jnp.mean(g.astype(jnp.float32), axis=1).astype(dt)

    return jax.tree.map(leaf, stacked)


def batch_means(stacked_grads, num_batches: int, *,
                scheme: str = "contiguous"):
    """Public helper: stacked (m, ...) pytree -> (k, ...) pytree of means."""
    m = _num_workers(stacked_grads)
    grouping = make_grouping(m, num_batches, scheme=scheme)
    return _apply_grouping(stacked_grads, grouping)


# ---------------------------------------------------------------------------
# aggregators

@register("mean", "plain average — the paper's Algorithm 1 (classical BGD), "
          "breakdown point 0: one Byzantine worker moves it arbitrarily",
          shard_contract="coordinate_wise")
def mean_aggregator(stacked_grads, **_kw):
    """Paper Algorithm 1: simple averaging — the failure-free baseline,
    broken by a single Byzantine report (§1.3)."""
    def leaf(g):
        return jnp.mean(g.astype(jnp.float32), axis=0).astype(g.dtype)
    return jax.tree.map(leaf, stacked_grads)


def resolve_round_backend(round_backend: str | None, *, num_batches: int,
                          total_dim: int | None = None,
                          num_workers: int = 0,
                          target_backend: str | None = None,
                          partitioned: bool = False) -> str:
    """Map the ``round_backend`` switch to a concrete path.

    ``auto``/None picks the fused Pallas kernel on TPU backends and the
    reference jnp pipeline elsewhere.  ``target_backend`` names the backend
    the lowered program will RUN on; auto-dispatch keys off it instead of
    the host's ``jax.default_backend()``, so a dry-run sweep lowering TPU
    mesh programs from a CPU host resolves the production path (previously
    those sweeps silently recorded the host's ``reference`` path).

    ``partitioned`` gradients (a ShardSpec with num_shards > 1) force
    ``reference``: the fused round kernel concatenates every leaf into one
    (m, d) block, which on partitioned slices would mean the very gather
    the shard-local contract exists to avoid.  Explicit fused requests get
    a warning; auto falls back silently.

    When ``total_dim`` is known, any fused selection (auto or explicit)
    falls back to ``reference`` if the kernel's VMEM-resident footprint
    (``round.round_resident_bytes`` — the same formula the kernel's own
    guard uses) would blow its budget — silently for auto, with a warning
    for an explicit request."""
    if round_backend not in (None, "auto", "reference", "fused",
                             "fused_interpret"):
        raise ValueError(f"unknown round_backend {round_backend!r}")
    explicit = round_backend not in (None, "auto")
    if not explicit:
        if target_backend is None:
            import jax as _jax
            target_backend = _jax.default_backend()
        round_backend = "fused" if target_backend == "tpu" else "reference"
    if round_backend != "reference" and partitioned:
        if explicit:
            import warnings
            warnings.warn(
                f"round_backend={round_backend!r} requested but the stacked "
                "gradients are partitioned over param shards; the fused "
                "round kernel's leaf concatenation would gather them — "
                "using 'reference'", stacklevel=3)
        return "reference"
    if round_backend != "reference" and total_dim is not None:
        from repro.kernels.geomed import round as round_kernel
        if not round_kernel.fits_vmem(num_workers, num_batches, total_dim):
            if explicit:
                import warnings
                warnings.warn(
                    f"round_backend={round_backend!r} requested but the "
                    f"(k={num_batches}, d={total_dim}) block exceeds the "
                    "fused kernel's VMEM budget; using 'reference'",
                    stacklevel=3)
            return "reference"
    return round_backend


def _total_dim(stacked) -> int:
    return sum(int(np.prod(l.shape[1:], dtype=np.int64)) if l.ndim > 1 else 1
               for l in jax.tree.leaves(stacked))


@register("gmom", "geometric median of means — the paper's Algorithm 2 "
          "(fused Pallas round kernel on TPU, jnp reference elsewhere)",
          needs_num_byzantine=True, needs_grouping=True,
          needs_shard_spec=True, shard_contract="norm_based",
          sanitization_point="weiszfeld")
def gmom_aggregator(stacked_grads, *, num_batches: int | None = None,
                    num_byzantine: int = 0, epsilon: float = 0.1,
                    grouping_scheme: str = "contiguous",
                    trim_multiplier: float | None = 3.0,
                    max_iters: int = 64, tol: float = 1e-8,
                    round_backend: str | None = "auto",
                    shard_spec=None, info: dict | None = None, **_kw):
    """Paper Algorithm 2 step 4: A_k(g) = med{batch means}, with the
    Remark-2 norm trimming applied as zero Weiszfeld weights.

    ``round_backend`` selects the hot-path lowering (see module docstring):
    the golden-trace-stable jnp ``reference`` pipeline, or the ``fused``
    Pallas round kernel that keeps means+trim+Weiszfeld VMEM-resident.
    A partitioned ``shard_spec`` forces ``reference`` (the kernel would
    gather) and routes every distance/norm reduction through
    :func:`repro.core.shard_aggregation.blocked_partial_sum` — the (k,)
    trim norms and one (k, k) Gram reduction before the Weiszfeld loop,
    nothing of size d ever crosses shards.

    ``info`` (a dict) receives the reference path's Weiszfeld step count
    under ``"weiszfeld_iters"``; the fused kernel keeps its loop counter in
    VMEM and reports none.  Each stage runs under a ``jax.named_scope``
    (``batch_means``, ``trim``, ``weiszfeld``; ``round_kernel`` in the
    kernel's front door) so a profile of the step finds it.
    """
    from repro.core import shard_aggregation as _sa
    m = _num_workers(stacked_grads)
    if num_batches is None:
        from repro.core.grouping import choose_num_batches
        num_batches = choose_num_batches(m, num_byzantine, epsilon=epsilon)
    if num_batches == 1:    # GMoM reduces to the mean (paper §2.1)
        return mean_aggregator(stacked_grads)
    backend = resolve_round_backend(
        round_backend, num_batches=num_batches,
        total_dim=_total_dim(stacked_grads), num_workers=m,
        target_backend=_sa.target_backend_of(shard_spec),
        partitioned=_sa.is_partitioned(shard_spec))
    if backend != "reference":
        from repro.kernels.geomed import round as round_kernel
        grouping = make_grouping(m, num_batches, scheme=grouping_scheme)
        return round_kernel.round_aggregate_pytree(
            stacked_grads, grouping, trim_multiplier=trim_multiplier,
            max_iters=max_iters, tol=tol,
            use_pallas=(backend == "fused"),
            interpret=(backend == "fused_interpret"))
    with jax.named_scope("batch_means"):
        means = batch_means(stacked_grads, num_batches,
                            scheme=grouping_scheme)
    weights = None
    if trim_multiplier is not None:
        with jax.named_scope("trim"):
            norms = batch_mean_norms(means, shard_spec=shard_spec)
            weights = trim_weights(norms, multiplier=trim_multiplier)
    with jax.named_scope("weiszfeld"):
        return geometric_median_pytree(means, weights=weights,
                                       max_iters=max_iters, tol=tol,
                                       shard_spec=shard_spec, info=info)


@register("geomed", "geometric median of the raw worker gradients — the "
          "k = m special case of GMoM (paper §2.1)",
          needs_shard_spec=True, shard_contract="norm_based",
          sanitization_point="weiszfeld")
def geomed_aggregator(stacked_grads, *, max_iters: int = 64,
                      tol: float = 1e-8, shard_spec=None,
                      info: dict | None = None, **_kw):
    """GMoM with every worker its own batch (k = m, paper §2.1): maximal
    robustness per report, no variance reduction from batching."""
    with jax.named_scope("weiszfeld"):
        return geometric_median_pytree(stacked_grads, max_iters=max_iters,
                                       tol=tol, shard_spec=shard_spec,
                                       info=info)


@register("coordinate_median", "coordinate-wise median — the marginal-"
          "median baseline of Yin et al. '18",
          shard_contract="coordinate_wise",
          sanitization_point="order_stat")
def coordinate_median_aggregator(stacked_grads, **_kw):
    """Per-coordinate median across workers (the marginal median): robust
    per coordinate, but ignores cross-coordinate structure — the
    comparison point for the paper's *geometric* (joint) median."""
    return jax.tree.map(lambda g: jnp.median(g, axis=0), stacked_grads)


@register("trimmed_mean", "coordinate-wise beta-trimmed mean "
          "[Yin et al. '18] — related-work baseline",
          needs_num_byzantine=True, shard_contract="coordinate_wise",
          sanitization_point="order_stat")
def trimmed_mean_aggregator(stacked_grads, *, trim_fraction: float = 0.1,
                            num_byzantine: int | None = None, **_kw):
    """Coordinate-wise mean after discarding the t largest and t smallest
    entries per coordinate (t = num_byzantine, else trim_fraction x m) —
    Yin et al. 2018's order-optimal rule under its own q < m/2 condition."""
    m = _num_workers(stacked_grads)
    t = num_byzantine if num_byzantine is not None else int(trim_fraction * m)
    t = min(t, (m - 1) // 2)

    def leaf(g):
        s = jnp.sort(g, axis=0)
        if t > 0:
            s = s[t:m - t]
        return jnp.mean(s.astype(jnp.float32), axis=0).astype(g.dtype)

    return jax.tree.map(leaf, stacked_grads)


@register("krum", "Krum selection rule [BMGS17] — the paper's closest "
          "related work; picks one whole gradient via the shard-local "
          "‖a‖²+‖b‖²−2a·b gram expansion (no flattened f32 copies)",
          needs_num_byzantine=True, needs_shard_spec=True,
          shard_contract="whole_gradient",
          sanitization_point="rank_select")
def krum_aggregator(stacked_grads, *, num_byzantine: int = 0,
                    shard_spec=None, **_kw):
    """Krum (Blanchard et al. '17): return the single worker gradient with
    the smallest sum of squared distances to its m - q - 2 nearest
    neighbours.  Selects a *received* gradient verbatim rather than
    averaging — robust, but discards the variance reduction of honest
    averaging the paper's GMoM keeps.

    The pairwise distances come from the ‖a‖² + ‖b‖² − 2a·b expansion of
    one (m, m) gram matrix, accumulated per leaf *in place* via
    ``dot_general`` with an f32 accumulator — no ``reshape(m, -1)`` and no
    full-leaf f32 copy, so peak memory is the stacked gradients themselves
    plus O(m²).  Under a partitioned ``shard_spec`` the per-shard partial
    grams combine through ONE (m, m) blocked reduction — the only
    collective krum needs.

    Requires ``m > q + 2`` so every score sums at least one *other*
    worker's distance; below that the neighbourhood is degenerate and
    Krum's guarantee is void, so we raise rather than silently clamp
    (mirroring the loud-validation style of ``RobustConfig``'s
    q <= (m-1)/2 tolerance condition).
    """
    from repro.core.shard_aggregation import blocked_partial_sum
    m = _num_workers(stacked_grads)
    closest = m - num_byzantine - 2
    if closest < 1:
        raise ValueError(
            f"krum needs m > q + 2 workers (got m={m}, q={num_byzantine}): "
            "the m - q - 2 nearest-neighbour score is degenerate and the "
            "selection guarantee [BMGS17] is void")

    def leaf_gram(g):
        axes = tuple(range(1, g.ndim))
        return jax.lax.dot_general(
            g, g, dimension_numbers=((axes, axes), ((), ())),
            preferred_element_type=jnp.float32)

    gram = blocked_partial_sum(shard_spec, jax.tree.leaves(stacked_grads),
                               leaf_gram, shape=(m, m), lead_axes=1)
    sq = jnp.diagonal(gram)
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    d2 = d2 + jnp.diag(jnp.full((m,), jnp.inf, jnp.float32))
    # score(i) = sum of the m - q - 2 smallest distances to others
    sorted_d2 = jnp.sort(d2, axis=1)
    scores = jnp.sum(sorted_d2[:, :closest], axis=1)
    winner = jnp.argmin(scores)
    return jax.tree.map(lambda g: jnp.take(g, winner, axis=0), stacked_grads)


@register("norm_clip_mean",
          "mean of gradients clipped to the median norm — KNOWN-UNSOUND "
          "vs small-norm attacks (alie, norm_stealth, inner_product)",
          needs_shard_spec=True, shard_contract="norm_based")
def norm_clip_mean_aggregator(stacked_grads, *, clip_multiplier: float = 1.0,
                              shard_spec=None, **_kw):
    """Mean of gradients clipped to ``clip_multiplier x median`` norm.

    .. warning:: **known-unsound vs. alie / norm_stealth.**  Clipping only
       bounds each report's *norm*; a coordinated small-norm attack (ALIE's
       mean - z.std report, norm_stealth hiding under the clip threshold,
       small-scale inner_product) passes through unclipped and biases the
       mean by O(q/m) per round — there is NO bounded-deviation guarantee.
       The defense matrix (tests/test_defense_matrix.py) deliberately
       excludes it from the ROBUST set; implementing the paper §6 combined
       selection rules against these adaptive attacks is an open ROADMAP
       item ("Defense gap found by the matrix tests").
    """
    norms = batch_mean_norms(stacked_grads, shard_spec=shard_spec)   # (m,)
    tau = clip_multiplier * jnp.median(norms)
    scale = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-12))

    def leaf(g):
        s = scale.reshape((-1,) + (1,) * (g.ndim - 1))
        return jnp.mean(g.astype(jnp.float32) * s, axis=0).astype(g.dtype)

    return jax.tree.map(leaf, stacked_grads)


# ---------------------------------------------------------------------------
# paper §6 (Discussion) future-work selection rules, implemented & answered
# empirically in benchmarks/selection_rules.py:
#   "A simple idea to defend against the relaxed Byzantine faults is to
#    select a subset of received gradients ... random selection ... or to
#    select the gradients of the small l2 norms."

@register("random_select",
          "paper §6 rule 1: average a random subset of the gradients "
          "(defends only the RELAXED adversary that cannot see the "
          "server's random bits — fails vs the paper's omniscient model)",
          needs_key=True, shard_contract="coordinate_wise")
def random_select_aggregator(stacked_grads, *, key=None,
                             subset_fraction: float = 0.5, **_kw):
    """Average a uniformly random subset (paper §6, rule 1).  Only defends
    the RELAXED adversary: the paper's omniscient model sees the server's
    random bits (our attacks receive the same ``key``), adapts, and wins —
    the §6 caveat the selection_rules benchmark demonstrates.

    ``key`` is required: the engine threads a fresh per-round key
    (``needs_key`` registry flag).  The old ``PRNGKey(0)`` fallback made
    the "random" subset deterministic and identical every round — a silent
    downgrade to a fixed selection rule — so a missing key now raises."""
    m = _num_workers(stacked_grads)
    n_sel = max(int(subset_fraction * m), 1)
    if key is None:
        raise ValueError(
            "random_select requires a PRNG key: without one the subset is "
            "identical every round (the aggregate_reported registry "
            "dispatch threads a fresh per-round key automatically)")
    scores = jax.random.uniform(key, (m,))
    sel = bottom_k_mask(scores, n_sel)     # exactly n_sel, even under ties

    def leaf(g):
        s = sel.reshape((-1,) + (1,) * (g.ndim - 1))
        acc = jnp.sum(g.astype(jnp.float32) * s, axis=0)
        return (acc / n_sel).astype(g.dtype)

    return jax.tree.map(leaf, stacked_grads)


@register("norm_select",
          "paper §6 rule 2: average the gradients with the smallest l2 "
          "norms — KNOWN-UNSOUND vs small-norm attacks (alie, "
          "norm_stealth); see benchmarks/selection_rules",
          needs_num_byzantine=True, needs_shard_spec=True,
          shard_contract="norm_based")
def norm_select_aggregator(stacked_grads, *, num_byzantine: int = 0,
                           shard_spec=None, **_kw):
    """Average the ``m - q`` smallest-norm gradients (paper §6, rule 2).

    .. warning:: **known-unsound vs. alie / norm_stealth.**  Selecting by
       small norm beats the classic large-norm attacks, but an adversary
       that *minimizes* its norm (ALIE, norm_stealth, small-scale
       inner_product) is preferentially SELECTED by this rule — its crafted
       rows rank below the honest ones and survive into the average, so the
       bounded-deviation property fails exactly on the attacks it is
       documented against in the defense matrix.  Excluded from ROBUST in
       tests/test_defense_matrix.py; the full fix (paper §6 combined
       selection rules) is a separate ROADMAP item.
    """
    m = _num_workers(stacked_grads)
    keep = max(m - max(num_byzantine, 1), 1)
    norms = batch_mean_norms(stacked_grads, shard_spec=shard_spec)   # (m,)
    # colluders reporting identical gradients tie in norm — rank-select so
    # exactly ``keep`` gradients are ever averaged.
    sel = bottom_k_mask(norms, keep)

    def leaf(g):
        s = sel.reshape((-1,) + (1,) * (g.ndim - 1))
        acc = jnp.sum(g.astype(jnp.float32) * s, axis=0)
        return (acc / keep).astype(g.dtype)

    return jax.tree.map(leaf, stacked_grads)


# ---------------------------------------------------------------------------
# SOUND combined selection rules — the paper §6 discussion made rigorous.
#
# PR 1's defense matrix proved the naive §6 selection rules above are NOT
# bounded under the adaptive small-norm attacks (alie / norm_stealth /
# inner_product): the adversary's crafted rows sit inside (or deliberately
# below) the honest norm envelope and survive one-sided selection or
# clipping.  The fix combines *filtering* with a rule that is itself
# robust, per the two natural ingredients from the related work:
#
# * coordinate-wise median / trimmed mean over the k BATCH MEANS
#   (Yin et al. '18, arXiv:1803.01498) — per-coordinate order statistics
#   over a fixed partition: at most q of k batches are contaminated, and
#   a per-coordinate median/trim over k values tolerates q < k/2 outliers
#   regardless of their norms;
# * a TWO-SIDED norm-envelope filter followed by GMoM (the filtering-style
#   combined rule of Su & Xu '18, arXiv:1804.10140): drop reports whose
#   norm deviates from the median norm by more than a MAD-scaled envelope
#   — both the classic huge-norm outliers AND the adversarially-small ones
#   (zero/stalling reports, small-scale inner_product) — then run the
#   paper's geometric-median-of-means on the survivors.  The filter only
#   ever *removes* outliers; boundedness never rests on it, because the
#   GMoM stage already tolerates q < k/2 contaminated batch means.
#
# All three are in the ROBUST set of tests/test_defense_matrix.py and the
# previously-skipped small-norm gap test asserts their bounded deviation.


@register("coord_median",
          "coordinate-wise median of the k batch means [Yin et al. '18] — "
          "sound combined rule: per-coordinate order statistics are immune "
          "to the small-norm attacks that break norm_select",
          needs_num_byzantine=True, needs_grouping=True,
          shard_contract="coordinate_wise",
          sanitization_point="order_stat")
def coord_median_aggregator(stacked_grads, *, num_batches: int | None = None,
                            num_byzantine: int = 0, epsilon: float = 0.1,
                            grouping_scheme: str = "contiguous", **_kw):
    """Coordinate-wise median over the k batch means (Yin et al. '18).

    Same batching discipline as ``gmom`` (fixed partition via
    ``core.grouping``, so at most q of k batch means are contaminated per
    round), but the median is marginal: each coordinate takes the median of
    its k batch-mean values.  A crafted report can only move a coordinate
    past the median by outnumbering the honest batches there — norm games
    (hiding under / ranking below the honest envelope) buy the adversary
    nothing, which is exactly the soundness the one-sided ``norm_select``
    lacks.

    Requires ``2q < k`` (the median's breakdown point): at q >= k/2 the
    contaminated batch means can straddle the median and drag it
    arbitrarily, so an out-of-guarantee configuration raises (same loud
    policy as ``coord_trimmed_mean`` / ``krum``) instead of silently
    emitting an adversary-dominated aggregate."""
    m = _num_workers(stacked_grads)
    if num_batches is None:
        from repro.core.grouping import choose_num_batches
        num_batches = choose_num_batches(m, num_byzantine, epsilon=epsilon)
    if 2 * num_byzantine >= num_batches:
        raise ValueError(
            f"coord_median needs 2q < k batches (got q={num_byzantine}, "
            f"k={num_batches}): the per-coordinate median's breakdown point "
            "is crossed and the Yin et al. '18 guarantee is void — "
            "increase num_batches or lower q")
    means = batch_means(stacked_grads, num_batches, scheme=grouping_scheme)
    return jax.tree.map(lambda z: jnp.median(z, axis=0), means)


@register("coord_trimmed_mean",
          "coordinate-wise q-trimmed mean of the k batch means "
          "[Yin et al. '18] — sound combined rule; trims the q largest AND "
          "q smallest per coordinate, unlike norm_select's one-sided cut",
          needs_num_byzantine=True, needs_grouping=True,
          shard_contract="coordinate_wise",
          sanitization_point="order_stat")
def coord_trimmed_mean_aggregator(stacked_grads, *,
                                  num_batches: int | None = None,
                                  num_byzantine: int = 0,
                                  epsilon: float = 0.1,
                                  grouping_scheme: str = "contiguous",
                                  trim_count: int | None = None, **_kw):
    """Coordinate-wise trimmed mean over the k batch means (Yin et al. '18,
    order-optimal under q < k/2).

    Per coordinate, sort the k batch-mean values and discard the t largest
    and t smallest before averaging, t = ``trim_count`` (default: q — the
    paper's fixed partition contaminates at most q batches per round).  The
    two-sided per-coordinate trim removes adversarial values wherever they
    sit — large, small, or sign-flipped — with no dependence on norms.

    Requires ``2t < k`` so at least one honest-majority value survives per
    coordinate; silently clamping t below the contamination level would
    emit an adversary-dominated aggregate while advertising ROBUST-set
    membership, so (like ``krum``'s degenerate-neighbourhood check) an
    out-of-guarantee configuration raises instead."""
    m = _num_workers(stacked_grads)
    if num_batches is None:
        from repro.core.grouping import choose_num_batches
        num_batches = choose_num_batches(m, num_byzantine, epsilon=epsilon)
    k = num_batches
    t = num_byzantine if trim_count is None else trim_count
    if t < 0 or 2 * t >= k:
        raise ValueError(
            f"coord_trimmed_mean needs 0 <= 2·trim_count < k batches (got "
            f"trim_count={t}, k={k}): trimming cannot cover q Byzantine "
            "batch means and the Yin et al. '18 guarantee is void — "
            "increase num_batches or lower q")
    means = batch_means(stacked_grads, k, scheme=grouping_scheme)

    def leaf(z):
        s = jnp.sort(z, axis=0)
        if t > 0:
            s = s[t:k - t]
        return jnp.mean(s.astype(jnp.float32), axis=0).astype(z.dtype)

    return jax.tree.map(leaf, means)


@register("norm_filter_gmom",
          "paper §6 combined rule [Su & Xu '18]: two-sided norm-envelope "
          "filter (drop reports whose norm sits outside median ± c·MAD — "
          "the huge AND the adversarially-small outliers), then GMoM on "
          "the surviving reports",
          needs_num_byzantine=True, needs_grouping=True,
          needs_shard_spec=True, shard_contract="norm_based",
          sanitization_point="weiszfeld")
def norm_filter_gmom_aggregator(stacked_grads, *,
                                num_batches: int | None = None,
                                num_byzantine: int = 0, epsilon: float = 0.1,
                                envelope_multiplier: float = 4.0,
                                grouping_scheme: str = "contiguous",
                                trim_multiplier: float | None = 3.0,
                                max_iters: int = 64, tol: float = 1e-8,
                                round_backend: str | None = "auto",
                                shard_spec=None, info: dict | None = None,
                                **_kw):
    """Two-sided norm filter -> geometric median of means (the §6
    "combined selection rule", in the filtering style of Su & Xu '18).

    Stage 1 — envelope filter: a report survives iff its l2 norm is within
    ``envelope_multiplier × MAD`` of the median report norm (MAD = median
    absolute deviation, a breakdown-point-1/2 spread estimate; a small
    relative slack keeps near-identical honest norms inside when the MAD
    underflows).  Unlike ``norm_select``'s bottom-k — which an adversary
    *minimizing* its norm is preferentially selected by — the envelope is
    two-sided: huge-norm attacks (sign_flip, mean_shift, noise) fall above
    it, adversarially-small reports (zero, shrunk inner_product) fall
    below.  Because at least half the reports sit within one MAD of the
    median by construction, at least ⌈m/2⌉ reports always survive.

    Stage 2 — GMoM on the survivors: each batch mean is re-averaged over
    its *surviving* members (a batch whose members were all filtered falls
    back to its unfiltered mean so shapes stay static), then the standard
    Remark-2 trim + Weiszfeld pipeline runs via :func:`gmom_aggregator` —
    including its ``round_backend`` dispatch, so the fused Pallas round
    kernel serves this rule on TPU unchanged.  The filter only ever drops
    outliers; boundedness under attacks that *survive* the envelope (alie,
    norm_stealth calibrated below the trim threshold, unit-scale
    inner_product) is inherited from the GMoM stage's q < k/2 median
    tolerance — this is what makes the combined rule sound where
    ``norm_select`` / ``norm_clip_mean`` are not.

    .. note:: with singleton batches (k = m, e.g. the group-mode production
       step where each batch-group gradient is its own report) every
       filtered report IS a fully-filtered batch, so the static-shape
       fallback makes stage 1 a structural no-op and the rule coincides
       with ``gmom`` (whose Remark-2 trim + median still provide the
       bounded-deviation guarantee).  The filter stage adds protection
       precisely when batches have >= 2 members: it restores the honest
       members' mean instead of letting one crafted report poison the
       whole batch mean.
    """
    m = _num_workers(stacked_grads)
    if num_batches is None:
        from repro.core.grouping import choose_num_batches
        num_batches = choose_num_batches(m, num_byzantine, epsilon=epsilon)
    k = num_batches
    norms = batch_mean_norms(stacked_grads, shard_spec=shard_spec)   # (m,)
    med = jnp.median(norms)
    mad = jnp.median(jnp.abs(norms - med))
    tau = envelope_multiplier * mad + 1e-3 * med + 1e-12
    keep = (jnp.abs(norms - med) <= tau).astype(jnp.float32)     # (m,)

    from repro.core.grouping import worker_batch_ids
    grouping = make_grouping(m, k, scheme=grouping_scheme)
    batch_id = jnp.asarray(worker_batch_ids(grouping))           # (m,) static
    sizes = jnp.asarray(grouping.batch_sizes, jnp.float32)       # (k,)
    counts = jax.ops.segment_sum(keep, batch_id, num_segments=k)  # (k,)
    # batch with every member filtered: fall back to its unfiltered mean
    keep_eff = jnp.where(counts[batch_id] > 0, keep, 1.0)
    counts_eff = jnp.where(counts > 0, counts, sizes)
    # Rescale rows so the UNWEIGHTED batch-mean machinery (reference
    # reshape-mean or the fused kernel's membership matmul / batch_sizes
    # division) yields the mean over the surviving members only:
    #   mean_l(g * r) = sum_{w in l, kept} g_w / count_l.
    rescale = keep_eff * sizes[batch_id] / counts_eff[batch_id]   # (m,)

    def leaf(g):
        r = rescale.astype(g.dtype).reshape((-1,) + (1,) * (g.ndim - 1))
        return g * r

    filtered = jax.tree.map(leaf, stacked_grads)
    return gmom_aggregator(filtered, num_batches=k,
                           num_byzantine=num_byzantine, epsilon=epsilon,
                           grouping_scheme=grouping_scheme,
                           trim_multiplier=trim_multiplier,
                           max_iters=max_iters, tol=tol,
                           round_backend=round_backend,
                           shard_spec=shard_spec, info=info)


# ---------------------------------------------------------------------------
# per-leaf ("blockwise") GMoM — the beyond-paper perf variant (DESIGN.md §3)

@register("gmom_per_leaf",
          "GMoM applied independently per parameter tensor — beyond-paper "
          "blockwise variant (DESIGN.md §3)",
          needs_num_byzantine=True, needs_grouping=True,
          needs_shard_spec=True, shard_contract="norm_based",
          sanitization_point="weiszfeld")
def gmom_per_leaf_aggregator(stacked_grads, *, num_batches: int | None = None,
                             num_byzantine: int = 0, epsilon: float = 0.1,
                             grouping_scheme: str = "contiguous",
                             max_iters: int = 64, tol: float = 1e-8,
                             shard_spec=None, **_kw):
    """Blockwise GMoM: one geometric median per parameter tensor instead of
    one in the concatenated R^d.  Cheaper to shard (medians run leaf-local)
    at the cost of the paper's joint-geometry guarantee holding only
    per block.

    Under a blocked ``shard_spec`` each leaf's median runs through the
    pytree Weiszfeld with blocked reductions (no ``reshape(k, -1)``, whose
    flatten would destroy the last-dim shard layout)."""
    m = _num_workers(stacked_grads)
    if num_batches is None:
        from repro.core.grouping import choose_num_batches
        num_batches = choose_num_batches(m, num_byzantine, epsilon=epsilon)
    if num_batches == 1:
        return mean_aggregator(stacked_grads)
    means = batch_means(stacked_grads, num_batches, scheme=grouping_scheme)

    if shard_spec is not None and shard_spec.blocked:
        def leaf_blocked(z):
            return geometric_median_pytree(
                {"x": z}, max_iters=max_iters, tol=tol,
                shard_spec=shard_spec)["x"]
        return jax.tree.map(leaf_blocked, means)

    def leaf(z):
        k = z.shape[0]
        flat = z.reshape(k, -1)
        med = geometric_median(flat.astype(jnp.float32),
                                  max_iters=max_iters, tol=tol)
        return med.astype(z.dtype).reshape(z.shape[1:])

    return jax.tree.map(leaf, means)


# ---------------------------------------------------------------------------
# communication-compressed rules (repro.core.compression)
#
# The paper's wire cost is O(md log N) bits per round (§1.4).  These two
# rules consume the compressed wire formats natively: when
# RobustConfig.compression matches the registered ``native_codec``,
# aggregate_reported hands them the encoded payload (plus a ``like=``
# shape/dtype template) instead of decoded floats.  With
# compression="none" they accept raw stacked gradients and behave
# identically — sign_sgd_majority votes on the raw signs, int8_gmom runs
# the plain gmom pipeline — so every existing harness (defense matrix,
# shard bitwise oracle, Layer B) covers them with no special casing.

@register("sign_sgd_majority",
          "coordinate-wise majority vote over 1-bit sign gradients "
          "[Jin et al. '19] — consumes the packed `sign` wire natively "
          "(votes on uint8 words, never reconstructs float gradients); "
          "shard-local with zero cross-shard collectives",
          shard_contract="coordinate_wise", native_codec="sign",
          sanitization_point="sign_vote")
def sign_sgd_majority_aggregator(stacked_grads, *, like=None, **_kw):
    """signSGD with majority vote (Jin et al. '19, arXiv 1902.10336):
    per coordinate, output −1 if a strict majority of the m reported sign
    bits are negative, else +1 (ties → +1).  Tolerant of q < m/2 blind
    sign-flippers; the vote-native ``sign_flip_targeted`` adversary breaks
    it exactly where the honest margin is ≤ 2q (the defense matrix pins
    that break point).

    The vote counting itself (exact integer sums over the worker axis)
    lives in ``repro.core.compression`` next to the packing code; both the
    raw and the packed entry points produce identical counts bit for bit.
    """
    from repro.core import compression
    if like is not None:
        return compression.majority_vote_packed(stacked_grads, like)
    return compression.majority_vote_signs(stacked_grads)


@register("int8_gmom",
          "GMoM on 8-bit stochastically-quantized reports: dequantizes the "
          "`int8_stochastic` wire (per-worker scales) then runs the full "
          "gmom pipeline incl. round_backend dispatch — 4× wire cut with "
          "the paper's Algorithm 2 guarantees on the dequantized reports",
          needs_num_byzantine=True, needs_grouping=True,
          needs_shard_spec=True, shard_contract="norm_based",
          native_codec="int8_stochastic",
          sanitization_point="weiszfeld")
def int8_gmom_aggregator(stacked_grads, *, like=None,
                         num_batches: int | None = None,
                         num_byzantine: int = 0, epsilon: float = 0.1,
                         grouping_scheme: str = "contiguous",
                         trim_multiplier: float | None = 3.0,
                         max_iters: int = 64, tol: float = 1e-8,
                         round_backend: str | None = "auto",
                         shard_spec=None, info: dict | None = None, **_kw):
    """Dequantize-then-GMoM: the int8 payload (q values + per-worker
    scales) is expanded back to ``like``'s dtype in-rule, then the paper's
    Algorithm 2 pipeline runs unchanged — including the ``round_backend``
    dispatch to the fused Pallas round kernel and the shard-local blocked
    reductions.  With ``compression="none"`` (``like=None``) the reports
    arrive unquantized and this IS gmom."""
    if like is not None:
        from repro.core import compression
        stacked_grads = compression.get_codec("int8_stochastic").decode(
            stacked_grads, like)
    return gmom_aggregator(stacked_grads, num_batches=num_batches,
                           num_byzantine=num_byzantine, epsilon=epsilon,
                           grouping_scheme=grouping_scheme,
                           trim_multiplier=trim_multiplier,
                           max_iters=max_iters, tol=tol,
                           round_backend=round_backend,
                           shard_spec=shard_spec, info=info)
