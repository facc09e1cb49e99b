"""Collective extraction from both IR levels (Layer B's measuring stick).

Two views of the same question — "what crosses shards, and how big is it?":

* :func:`jaxpr_collectives` walks a (closed) jaxpr recursively (while/scan/
  cond/shard_map sub-jaxprs included) and returns every collective-primitive
  equation with its output shapes and mesh axes.  This is the *pre-XLA*
  view: exactly the collectives the aggregation code asked for.
* :func:`hlo_collective_shapes` / the reused
  :func:`repro.roofline.hlo_parser.analyze` read the compiled per-device
  HLO text — the *post-XLA* view, catching collectives the partitioner
  inserted on its own.

The contract analyzer (``repro.verify.contracts``) requires both views to
agree with the registered aggregator's declared ``shard_contract``.
"""

from __future__ import annotations

import dataclasses

from repro.roofline import hlo_parser

# collective primitive names as they appear in jaxprs (older releases
# spelled shard_map's psum ``psum2``; jax 0.9 keeps ``psum`` and adds the
# ``*_invariant`` forms of the varying-axes system).
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "psum_invariant", "pmax", "pmin",
    "all_gather", "all_gather_invariant", "all_to_all", "ppermute",
    "pbroadcast", "reduce_scatter", "psum_scatter", "pgather",
})


@dataclasses.dataclass(frozen=True)
class CollectiveUse:
    prim: str
    axes: tuple[str, ...]
    out_shapes: tuple[tuple[int, ...], ...]

    @property
    def elements(self) -> int:
        total = 0
        for shape in self.out_shapes:
            n = 1
            for d in shape:
                n *= int(d)
            total += n
        return total


def _sub_jaxprs(eqn):
    """Every jaxpr nested in an equation's params (while/scan/cond/pjit/
    shard_map/custom_* — matched structurally, not by primitive name, so
    version drift in param spellings cannot hide a nesting level)."""
    subs = []

    def visit(val):
        if hasattr(val, "jaxpr") and hasattr(val.jaxpr, "eqns"):
            subs.append(val.jaxpr)          # ClosedJaxpr
        elif hasattr(val, "eqns"):
            subs.append(val)                # raw Jaxpr
        elif isinstance(val, (tuple, list)):
            for v in val:
                visit(v)

    for val in eqn.params.values():
        visit(val)
    return subs


def jaxpr_collectives(jaxpr) -> list[CollectiveUse]:
    """All collective-primitive uses in ``jaxpr`` (recursive)."""
    if hasattr(jaxpr, "jaxpr"):            # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    uses: list[CollectiveUse] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            axes = eqn.params.get("axes",
                                  eqn.params.get("axis_name", ()))
            if isinstance(axes, str):
                axes = (axes,)
            axes = tuple(str(a) for a in axes)
            shapes = tuple(tuple(int(d) for d in v.aval.shape)
                           for v in eqn.outvars)
            uses.append(CollectiveUse(prim=name, axes=axes,
                                      out_shapes=shapes))
        for sub in _sub_jaxprs(eqn):
            uses.extend(jaxpr_collectives(sub))
    return uses


def hlo_collective_shapes(hlo_text: str) -> list[tuple[str, tuple[int, ...]]]:
    """(op, result dims) for every collective instruction in the HLO text,
    sorted — the d-independence comparison key for the compiled view."""
    out = []
    for comp in hlo_parser.parse_computations(hlo_text).values():
        for ins in comp.instrs:
            base = ins.op.replace("-start", "")
            if base not in hlo_parser._COLLECTIVES or \
                    ins.op.endswith("-done"):
                continue
            for _, dims in hlo_parser._SHAPE_RE.findall(ins.result_text):
                shape = tuple(int(d) for d in dims.split(",") if d)
                out.append((base, shape))
    return sorted(out)


def hlo_collective_bytes(hlo_text: str) -> float:
    """Trip-count-corrected collective bytes of the compiled module (reuses
    the roofline cost walker)."""
    return hlo_parser.analyze(hlo_text).collective_bytes
