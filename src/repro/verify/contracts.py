"""Layer B: verify every registered aggregator's *declared* shard contract
against what it actually lowers to.

For each aggregator the analyzer traces the exact production path — the
``make_sharded_aggregate`` shard-local body under ``shard_map`` on a real
(host-virtualized) mesh — and checks the declaration:

* ``coordinate_wise`` — ZERO cross-shard collectives, in both the jaxpr
  (what the code asked for) and the compiled HLO (what the partitioner
  actually emitted).  RV201 on violation.
* ``norm_based`` — collectives allowed, but they must be *d-independent*:
  tracing at hidden size d and 2d must produce identical collective
  shapes (the (k,)/(m,)/(m,m) partial reductions of PAPER.md §Thm 3 —
  never O(d) traffic), and no single collective may move more than
  ``num_shards * m * m`` elements.  RV202 on violation.
* ``whole_gradient`` — selection rules (krum) that output one worker's
  whole gradient; their *collectives* must still be d-independent (the
  selection score is a psum'd (m, m) distance partial; the winning
  gradient itself is taken shard-locally).  RV202 on violation.

Independently, a **determinism audit** (RV203) traces the gathered
``"virtual"``-mode oracle with a uniquely-sized shard axis (S=5, chosen to
collide with no worker/group/leaf extent) and flags any ``reduce_sum`` /
``reduce_prod`` over an extent-5 axis: such a reduction re-introduces the
XLA reassociation freedom that the unrolled ``chain_sum`` of
``core/shard_aggregation.py`` exists to remove (PR 6's 1-ulp drift bug).

No literal PRNG seeds here — this module is linted by its own Layer A
(RV102); harness arrays are deterministic arange/sin fills and the traced
key is built from a caller-supplied seed.
"""

from __future__ import annotations

import numpy as np

from repro.verify import collectives
from repro.verify.rules import Finding

# harness geometry: m workers, k groups, one Byzantine; leaf last-dims are
# multiples of 8 so every supported shard count (2/4/8) divides them.
HARNESS_M = 8
HARNESS_K = 4
HARNESS_Q = 1

# determinism-audit geometry: shard count 5 appears as NO other extent
# (workers 12, groups 6, trim slice 4, leaf dims 15/3/10 and their
# per-shard slices 3/2) — so an extent-5 reduction can only be a
# reduction over the shard-stack axis.
DET_SHARDS = 5
DET_M = 12
DET_K = 6


def _fill(shape, salt: int):
    import jax.numpy as jnp
    n = int(np.prod(shape)) if shape else 1
    base = np.arange(n, dtype=np.float64) * 0.37 + float(salt) * 1.61
    return jnp.asarray(np.sin(base).reshape(shape), jnp.float32)


def harness_tree(m: int, scale: int):
    """Stacked-gradient pytree; ``scale`` multiplies the sharded last dims
    (the d-independence probe)."""
    return {
        "w": _fill((m, 16 * scale), 3),
        "b": {"x": _fill((m, 4, 8 * scale), 5)},
        "s": _fill((m,), 7),
    }


def harness_cfg(name: str, *, m: int = HARNESS_M, k: int = HARNESS_K,
                q: int = HARNESS_Q, codec: str | None = None,
                round_backend: str = "auto"):
    from repro.core import aggregators
    from repro.core.robust_train import RobustConfig
    # an aggregator with a native wire codec is traced through its
    # COMPRESSED production path (encode -> payload -> native consume):
    # that is the path the contract claims are about — sign_sgd_majority's
    # zero-collective guarantee must hold for the packing + vote, and
    # int8_gmom's d-independence must cover the per-worker scale combine.
    # Layer C's full matrix overrides ``codec`` to probe every wire format.
    if codec is None:
        codec = aggregators.get_aggregator(name).native_codec or "none"
    return RobustConfig(num_workers=m, num_byzantine=q, num_batches=k,
                        attack="none", aggregator=name,
                        gmom_max_iters=8, gmom_tol=1e-7,
                        compression=codec, round_backend=round_backend)


def _specs(tree, axis: str):
    import jax
    from jax.sharding import PartitionSpec as P

    def in_spec(x):
        if x.ndim == 1:
            return P(None)                       # (m,) — replicated
        return P(*((None,) * (x.ndim - 1) + (axis,)))

    def out_spec(x):
        if x.ndim == 0:
            return P()
        return P(*((None,) * (x.ndim - 1) + (axis,)))

    return jax.tree.map(in_spec, tree), out_spec


def _sharded_fn(name: str, num_shards: int, scale: int, *, seed: int,
                codec: str | None = None):
    """(traceable fn, example args) — the production shard_map path."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.core.robust_train import make_sharded_aggregate
    from repro.launch.mesh import make_mesh

    axis = "model"
    cfg = harness_cfg(name, codec=codec)
    stacked = harness_tree(HARNESS_M, scale)
    key = jax.random.PRNGKey(seed)
    mesh = make_mesh((num_shards,), (axis,))
    in_specs, _ = _specs(stacked, axis)
    # aggregation drops the leading worker axis of every leaf — derive the
    # output specs structurally rather than via eval_shape (which would run
    # the aggregator body outside the mesh context and break on any rule
    # that uses collectives)
    out_specs = jax.tree.map(
        lambda x: (P() if x.ndim == 1
                   else P(*((None,) * (x.ndim - 2) + (axis,)))),
        stacked)
    agg = make_sharded_aggregate(cfg, mesh, axis=axis)
    fn = jax.shard_map(agg, mesh=mesh, in_specs=(in_specs, P(None)),
                       out_specs=out_specs, check_vma=False)
    return fn, (stacked, key)


def _anchor(name: str) -> str:
    return f"<aggregator:{name}>"


# --------------------------------------------------------------------------
# trace cache
#
# One production trace serves every rule that inspects it: RV201/RV202 read
# the shard_map jaxpr + HLO, RV203 the virtual-mode jaxpr, and Layer C's
# taint pass re-walks the very same jaxprs with influence labels.  Tracing
# (and especially XLA compilation) dominates `--strict` wall time, so each
# (kind, aggregator, codec, shards, scale, seed) cell is traced exactly
# once per process.

_TRACE_CACHE: dict[tuple, object] = {}


def clear_trace_cache() -> None:
    """Drop every cached trace (tests re-registering dummy aggregators)."""
    _TRACE_CACHE.clear()


def _resolve_codec(name: str, codec: str | None) -> str:
    if codec is not None:
        return codec
    from repro.core import aggregators
    return aggregators.get_aggregator(name).native_codec or "none"


def traced_shard_map(name: str, *, num_shards: int, scale: int, seed: int,
                     codec: str | None = None):
    """(closed_jaxpr, out_shape, example_args) for the shard_map path."""
    import jax
    codec = _resolve_codec(name, codec)
    key = ("shard_map", name, codec, num_shards, scale, seed)
    if key not in _TRACE_CACHE:
        fn, args = _sharded_fn(name, num_shards, scale, seed=seed,
                               codec=codec)
        jaxpr, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        _TRACE_CACHE[key] = (jaxpr, out_shape, args)
    return _TRACE_CACHE[key]


def compiled_shard_map_text(name: str, *, num_shards: int, scale: int,
                            seed: int, codec: str | None = None) -> str:
    """Compiled-HLO text for the shard_map path (the expensive view)."""
    import jax
    codec = _resolve_codec(name, codec)
    key = ("hlo", name, codec, num_shards, scale, seed)
    if key not in _TRACE_CACHE:
        fn, args = _sharded_fn(name, num_shards, scale, seed=seed,
                               codec=codec)
        _TRACE_CACHE[key] = jax.jit(fn).lower(*args).compile().as_text()
    return _TRACE_CACHE[key]


def traced_flat(name: str, *, seed: int, codec: str | None = None):
    """(closed_jaxpr, out_shape, example_args) for the unsharded
    ``aggregate_reported`` path on the Layer-B harness tree.

    ``round_backend`` is pinned to the jnp reference pipeline: the fused
    Pallas kernel is an opaque primitive to jaxpr-level analysis, and the
    reference path is the semantics the kernel is bit-tested against.
    """
    import jax
    from repro.core.robust_train import aggregate_reported
    codec = _resolve_codec(name, codec)
    key = ("flat", name, codec, None, 1, seed)
    if key not in _TRACE_CACHE:
        cfg = harness_cfg(name, codec=codec, round_backend="reference")
        stacked = harness_tree(HARNESS_M, 1)
        prng = jax.random.PRNGKey(seed)
        jaxpr, out_shape = jax.make_jaxpr(
            lambda s, k: aggregate_reported(s, cfg, key=k),
            return_shape=True)(stacked, prng)
        _TRACE_CACHE[key] = (jaxpr, out_shape, (stacked, prng))
    return _TRACE_CACHE[key]


def _fmt_uses(uses) -> str:
    return ", ".join(
        f"{u.prim}{list(u.out_shapes)}" for u in uses) or "none"


def check_aggregator(name: str, *, num_shards: int = 4, seed: int = 0,
                     hlo_both_scales: bool = False) -> list[Finding]:
    """All Layer-B findings for one registered aggregator."""
    import jax
    from repro.core import aggregators

    agg = aggregators.get_aggregator(name)
    contract = agg.shard_contract
    findings: list[Finding] = []
    anchor = _anchor(name)

    # --- jaxpr view at both scales (cached — Layer C re-walks these)
    uses = {}
    for scale in (1, 2):
        jaxpr, _, _ = traced_shard_map(name, num_shards=num_shards,
                                       scale=scale, seed=seed)
        uses[scale] = collectives.jaxpr_collectives(jaxpr)

    if contract == "coordinate_wise":
        if uses[1]:
            findings.append(Finding(
                rule="RV201", path=anchor, line=0, col=0,
                message=f"declared coordinate_wise but the jaxpr contains "
                        f"cross-shard collectives: {_fmt_uses(uses[1])}"))
    else:
        key1 = sorted((u.prim, u.out_shapes) for u in uses[1])
        key2 = sorted((u.prim, u.out_shapes) for u in uses[2])
        if key1 != key2:
            findings.append(Finding(
                rule="RV202", path=anchor, line=0, col=0,
                message=f"collective shapes change with hidden size d "
                        f"(d-dependent traffic): d -> {_fmt_uses(uses[1])} "
                        f"vs 2d -> {_fmt_uses(uses[2])}"))
        if contract == "norm_based":
            cap = num_shards * HARNESS_M * HARNESS_M
            for u in uses[1]:
                if u.elements > cap:
                    findings.append(Finding(
                        rule="RV202", path=anchor, line=0, col=0,
                        message=f"norm_based collective {u.prim}"
                                f"{list(u.out_shapes)} moves {u.elements} "
                                f"elements > cap {cap} "
                                f"(num_shards*m*m) — partial reductions "
                                f"must stay (k,)/(m,)/(m,m)-shaped"))

    # --- compiled-HLO view (the partitioner can insert collectives the
    # jaxpr never asked for)
    hlo = {}
    for scale in (1, 2) if hlo_both_scales else (1,):
        hlo[scale] = compiled_shard_map_text(
            name, num_shards=num_shards, scale=scale, seed=seed)

    if contract == "coordinate_wise":
        nbytes = collectives.hlo_collective_bytes(hlo[1])
        if nbytes > 0:
            shapes = collectives.hlo_collective_shapes(hlo[1])
            findings.append(Finding(
                rule="RV201", path=anchor, line=0, col=0,
                message=f"declared coordinate_wise but the compiled HLO "
                        f"moves {nbytes:.0f} collective bytes: {shapes}"))
    elif hlo_both_scales:
        s1 = collectives.hlo_collective_shapes(hlo[1])
        s2 = collectives.hlo_collective_shapes(hlo[2])
        if s1 != s2:
            findings.append(Finding(
                rule="RV202", path=anchor, line=0, col=0,
                message=f"compiled collective shapes change with hidden "
                        f"size d: {s1} vs {s2}"))

    findings.extend(audit_determinism(name, seed=seed))
    return findings


# --------------------------------------------------------------------------
# determinism audit (RV203)


def _walk_eqns(jaxpr):
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in collectives._sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def audit_determinism(name: str, *, seed: int = 0) -> list[Finding]:
    """Trace the gathered virtual-mode oracle with the uniquely-sized
    shard axis and flag reassociation-sensitive reductions over it."""
    import jax
    from repro.core.robust_train import aggregate_reported
    from repro.core.shard_aggregation import ShardSpec

    cache_key = ("virtual", name, None, DET_SHARDS, 1, seed)
    try:
        if cache_key in _TRACE_CACHE:
            jaxpr = _TRACE_CACHE[cache_key]
        else:
            cfg = harness_cfg(name, m=DET_M, k=DET_K)
            stacked = {
                "w": _fill((DET_M, 15), 11),
                "b": {"x": _fill((DET_M, 3, 10), 13)},
                "s": _fill((DET_M,), 17),
            }
            key = jax.random.PRNGKey(seed)
            spec = ShardSpec(num_shards=DET_SHARDS, mode="virtual",
                             axis="model")
            jaxpr = jax.make_jaxpr(
                lambda s, k: aggregate_reported(
                    s, cfg, key=k, shard_spec=spec))(stacked, key)
            _TRACE_CACHE[cache_key] = jaxpr
    except Exception as e:  # noqa: BLE001
        # an aggregator that cannot trace under the meshless virtual spec
        # (e.g. a hardcoded collective) also breaks the sharded-vs-gathered
        # bit-equality oracle — that IS a contract violation, not an
        # internal error of the checker
        return [Finding(
            rule="RV203", path=_anchor(name), line=0, col=0,
            message=f"gathered virtual-mode oracle failed to trace "
                    f"({type(e).__name__}: {e}) — every aggregator must "
                    f"route cross-shard work through the ShardSpec so the "
                    f"single-device oracle stays traceable")]

    findings: list[Finding] = []
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name not in ("reduce_sum", "reduce_prod"):
            continue
        shape = tuple(eqn.invars[0].aval.shape)
        axes = eqn.params.get("axes", ())
        bad = [a for a in axes if shape[a] == DET_SHARDS]
        if bad:
            findings.append(Finding(
                rule="RV203", path=_anchor(name), line=0, col=0,
                message=f"{eqn.primitive.name} over axis {bad} of shape "
                        f"{shape} reduces the {DET_SHARDS}-extent shard "
                        f"stack — use the unrolled chain_sum of "
                        f"core/shard_aggregation.py (bit-stability)"))
    return findings
