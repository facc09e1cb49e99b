"""Jaxpr-level influence lattice for the Layer C Byzantine taint analysis.

This module is the *engine*: it propagates adversary-influence labels
through a traced jaxpr, one equation at a time, with no knowledge of
aggregator names or registry metadata.  ``repro.verify.taint`` builds the
harnesses (which inputs are adversary-controlled) and turns the resulting
output labels into RV301/RV302/RV303 findings.

The lattice tracks, per value, the worst-case influence a SINGLE Byzantine
worker's report can exert on it:

* ``CLEAN``   — no dependence on any adversary-controlled input.
* ``BOUNDED`` — depends on adversary inputs, but every path crosses an op
  whose per-worker influence is bounded no matter what the worker sends
  (an order statistic, a rank selection, a clip against a robust
  threshold, a sign/majority vote, or a Weiszfeld reweighting).
* ``RAW``     — at least one path lets a single report move the value
  arbitrarily far (sums, means, scale multiplies, dequantize-by-scale).

Alongside the level each label carries ``kinds`` — which bounded-op
families appear on the dataflow (``order_stat`` / ``rank_select`` /
``sign_vote`` / ``clip`` / ``weiszfeld``) — and ``sources`` — which
adversary surfaces feed it (``report`` / ``age`` / ``attack_state``).

Design rules (see docs/STATIC_ANALYSIS.md for the full table and the
documented imprecisions):

* The DEFAULT transfer for every primitive is ``join`` (max level, union
  kinds/sources).  In particular ``mul(RAW, mask)`` stays RAW — masking a
  raw report by a robust 0/1 mask rescales it, it does not bound it
  (exactly the ``norm_select`` unsoundness of PR 5), and an int8 wire
  scale derived via ``reduce_max`` over a raw report stays RAW.
* Only a handful of primitives may *demote* RAW to BOUNDED, and each
  demotion records its kind so RV303 can compare discovered kinds against
  the registry's declared ``sanitization_point``.
* Composite sanitizers that are invisible at single-primitive granularity
  (the Weiszfeld ``1/dist`` reweighting inside a ``while`` loop) are
  recognized structurally by a flag-propagation pass over the loop body —
  still with zero name-based special cases.  A loop that fires and also
  carries the Weiszfeld *coefficients* (the Gram form) marks them
  ``coef``; the combine that multiplies them into the raw reports after
  the loop is what demotes.
"""

from __future__ import annotations

import dataclasses
from typing import Any

CLEAN = 0
BOUNDED = 1
RAW = 2

_LEVEL_NAMES = {CLEAN: "CLEAN", BOUNDED: "BOUNDED", RAW: "RAW"}

#: the closed set of bounded-op families a demotion may record; the
#: registry's ``sanitization_point`` declarations are validated against it.
SANITIZER_KINDS = ("clip", "order_stat", "rank_select", "sign_vote",
                   "weiszfeld")


@dataclasses.dataclass(frozen=True)
class Label:
    level: int = CLEAN
    kinds: frozenset = frozenset()
    sources: frozenset = frozenset()
    #: a Weiszfeld coefficient vector: the normalized inverse-distance
    #: weights carried out of a loop the detector fired on, or a slice,
    #: cast or reshape of them (see ``_COEF_PRIMS``)
    coef: bool = False

    def join(self, other: "Label") -> "Label":
        if other is CLEAN_LABEL:
            return self
        if self is CLEAN_LABEL:
            return other
        return Label(level=max(self.level, other.level),
                     kinds=self.kinds | other.kinds,
                     sources=self.sources | other.sources,
                     coef=self.coef and other.coef)

    def cap_bounded(self) -> "Label":
        """Influence through a comparison / index-valued op: the value
        range is tiny, so per-worker influence is bounded — but no
        sanitizer kind is credited (a bool is not a defense)."""
        if self.level <= BOUNDED:
            return self
        return Label(level=BOUNDED, kinds=self.kinds, sources=self.sources)

    def demote(self, kind: str) -> "Label":
        """Pass through a bounded-influence op of family ``kind``."""
        if self.level == CLEAN:
            return self
        return Label(level=BOUNDED, kinds=self.kinds | frozenset({kind}),
                     sources=self.sources)

    def describe(self) -> str:
        parts = [_LEVEL_NAMES[self.level]]
        if self.coef:
            parts.append("coef")
        if self.kinds:
            parts.append("kinds={" + ",".join(sorted(self.kinds)) + "}")
        if self.sources:
            parts.append("sources={" + ",".join(sorted(self.sources)) + "}")
        return " ".join(parts)


CLEAN_LABEL = Label()


def raw(source: str) -> Label:
    return Label(level=RAW, sources=frozenset({source}))


def join_all(labels) -> Label:
    out = CLEAN_LABEL
    for l in labels:
        out = out.join(l)
    return out


# --------------------------------------------------------------------------
# primitive tables

_ORDER_STAT_PRIMS = {"sort", "top_k", "approx_top_k"}

# bool- or index-valued outputs: tainted inputs can steer them, but the
# per-worker influence on the VALUE is bounded by the tiny output range.
_CAP_PRIMS = {"lt", "gt", "le", "ge", "eq", "ne", "argmin", "argmax",
              "reduce_and", "reduce_or", "is_finite", "sign"}

# value-selection by index; dynamic_update_slice is deliberately absent
# (its update operand embeds a raw VALUE — default join applies).
_GATHER_PRIMS = {"gather", "dynamic_slice"}

# shape-only ops a coefficient vector keeps its ``coef`` mark through
# (``weighted_sum``'s ``w.astype(dtype)[i]``); any other op drops it
_COEF_PRIMS = {"convert_element_type", "slice", "squeeze", "reshape",
               "broadcast_in_dim"}

# higher-order call-like primitives: the sub-jaxpr binds eqn.invars
# positionally (jaxpr param key varies by primitive / jax version).
_SUBJAXPR_PARAM_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _is_literal(v) -> bool:
    return hasattr(v, "val")


class _Env:
    __slots__ = ("m",)

    def __init__(self):
        self.m: dict[Any, Label] = {}

    def read(self, v) -> Label:
        if _is_literal(v):
            return CLEAN_LABEL
        return self.m.get(v, CLEAN_LABEL)

    def write(self, v, label: Label) -> None:
        self.m[v] = label


# --------------------------------------------------------------------------
# per-equation transfer

def _transfer(name: str, eqn, ins: list[Label]) -> Label:
    """Label for every outvar of a first-order equation."""
    if name in _ORDER_STAT_PRIMS:
        # sort/top_k: any single report moves the output by at most one
        # rank slot — the PAPER.md Remark-2 / Yin'18 coordinate-wise
        # argument.  Covers the co-sorted argsort operand and the index
        # output alike.
        return join_all(ins).demote("order_stat")
    if name in _CAP_PRIMS:
        # `sign` is capped (range {-1,0,1}) but does NOT credit the
        # sign_vote kind by itself: a per-worker sign is sanitized only
        # once it feeds a majority vote (the select_n rule below).
        return join_all(ins).cap_bounded()
    if name == "clamp":
        lo, x, hi = ins[0], ins[1], ins[2]
        if lo.level < RAW and hi.level < RAW:
            return join_all(ins).demote("clip")
        return join_all(ins)
    if name == "select_n":
        pred, vals = ins[0], ins[1:]
        if all(v.level == CLEAN for v in vals):
            # where(vote_condition, ±const, ∓const): the report only
            # steers a choice among clean constants — the majority-vote
            # shape, however `signbit`/threshold lowered upstream.
            if pred.level == CLEAN:
                return CLEAN_LABEL
            return Label(level=BOUNDED,
                         kinds=pred.kinds | frozenset({"sign_vote"}),
                         sources=pred.sources)
        return join_all(vals).join(pred.cap_bounded())
    if name in _GATHER_PRIMS:
        operand, idx = ins[0], join_all(ins[1:])
        if idx.level == CLEAN:
            return operand
        # Tainted index over any operand: the adversary picks WHICH row
        # wins, not its value — bounded per-worker influence, credited as
        # rank selection (krum's winner-take).  Documented caveat: this
        # presumes the selection score itself is robust; the verbatim
        # selected gradient is still one worker's report.
        return Label(level=BOUNDED,
                     kinds=operand.kinds | idx.kinds
                           | frozenset({"rank_select"}),
                     sources=operand.sources | idx.sources)
    if name in ("mul", "dot_general") and len(ins) == 2:
        a, b = ins
        if (a.coef and b.level == RAW) or (b.coef and a.level == RAW):
            # the Gram form's combine y = Σ c_j x_j: Weiszfeld coefficients
            # (c_j ∝ w_j / ‖x_j − y‖) into the raw reports — the same
            # reweighted report sum the point form makes inside its loop
            return Label(level=BOUNDED,
                         kinds=a.kinds | b.kinds | frozenset({"weiszfeld"}),
                         sources=a.sources | b.sources)
    # default: join.  Sums, means, muls, dots, scatters, bitwise ops,
    # conversions, broadcasts — none of them bound per-worker influence.
    out = join_all(ins)
    if out.coef and name not in _COEF_PRIMS:
        out = dataclasses.replace(out, coef=False)
    return out


# --------------------------------------------------------------------------
# jaxpr walk

def _closed_parts(closed):
    """(raw_jaxpr) for either a ClosedJaxpr or a raw Jaxpr param."""
    return closed.jaxpr if hasattr(closed, "jaxpr") else closed


def run_jaxpr(jaxpr, in_labels: list[Label],
              capture: dict | None = None) -> list[Label]:
    """Propagate labels through one (raw or closed) jaxpr.

    ``in_labels`` matches ``jaxpr.invars``; constvars are CLEAN (they are
    trace-time constants, not runtime adversary inputs).  When ``capture``
    is given, every intermediate var's label is recorded into it (used by
    the Weiszfeld detector).
    """
    jaxpr = _closed_parts(jaxpr)
    if len(in_labels) != len(jaxpr.invars):
        raise ValueError(
            f"label/invar arity mismatch: {len(in_labels)} labels for "
            f"{len(jaxpr.invars)} invars")
    env = _Env()
    if capture is not None:
        env.m = capture
    for v in jaxpr.constvars:
        env.write(v, CLEAN_LABEL)
    for v, lab in zip(jaxpr.invars, in_labels):
        env.write(v, lab)
    for eqn in jaxpr.eqns:
        _step(eqn, env)
    return [env.read(v) for v in jaxpr.outvars]


def _is_bool_var(v) -> bool:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and dtype == bool


def _step(eqn, env: _Env) -> None:
    name = eqn.primitive.name
    ins = [env.read(v) for v in eqn.invars]
    if name == "while":
        outs = _while(eqn, ins)
    elif name == "scan":
        outs = _scan(eqn, ins)
    elif name == "cond":
        outs = _cond(eqn, ins)
    else:
        outs = _call_like(eqn, ins)
        if outs is None:
            lab = _transfer(name, eqn, ins)
            outs = [lab] * len(eqn.outvars)
    for v, lab in zip(eqn.outvars, outs):
        # a boolean's VALUE range is {0,1}: whatever fed it, one worker's
        # per-value influence is bounded (and sums of bools stay bounded).
        # Applied per-outvar on dtype, not per-primitive, so and/or/not
        # chains over predicates (attack strike logic, arrival masks)
        # never spuriously escalate to RAW.
        if _is_bool_var(v):
            lab = lab.cap_bounded()
        env.write(v, lab)


def _call_like(eqn, ins: list[Label]) -> list[Label] | None:
    """Descend into pjit/closed_call/remat/custom_*/shard_map bodies by
    positional binding; None when the eqn has no sub-jaxpr.  An arity
    mismatch (exotic primitive) falls back to a conservative join-all."""
    subs = []
    for key in _SUBJAXPR_PARAM_KEYS:
        sub = eqn.params.get(key) if eqn.params else None
        if sub is not None:
            subs.append(sub)
    if not subs:
        if _has_any_subjaxpr(eqn):
            j = join_all(ins)
            return [j] * len(eqn.outvars)
        return None
    for sub in subs:
        jaxpr = _closed_parts(sub)
        if len(jaxpr.invars) == len(ins):
            outs = run_jaxpr(jaxpr, ins)
            if len(outs) >= len(eqn.outvars):
                return outs[:len(eqn.outvars)]
    j = join_all(ins)
    return [j] * len(eqn.outvars)


def _has_any_subjaxpr(eqn) -> bool:
    if not eqn.params:
        return False
    for val in eqn.params.values():
        for v in (val if isinstance(val, (tuple, list)) else (val,)):
            if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                return True
    return False


_FIXPOINT_LIMIT = 64


def _while(eqn, ins: list[Label]) -> list[Label]:
    cn = eqn.params["cond_nconsts"]
    bn = eqn.params["body_nconsts"]
    body = eqn.params["body_jaxpr"]
    body_consts = ins[cn:cn + bn]
    carry = list(ins[cn + bn:])
    for _ in range(_FIXPOINT_LIMIT):
        outs = run_jaxpr(body, body_consts + carry)
        new = [c.join(o) for c, o in zip(carry, outs)]
        if new == carry:
            break
        carry = new
    if any(l.level == RAW for l in carry):
        flags = _weiszfeld_flags(body, body_consts + carry, bn)
        if any("wprod" in f for f in flags):
            carry = [dataclasses.replace(l.demote("weiszfeld"),
                                         coef="wprod" not in f
                                         and "inv_w" in f)
                     if l.level == RAW else l
                     for l, f in zip(carry, flags)]
    return carry


def _scan(eqn, ins: list[Label]) -> list[Label]:
    nc = eqn.params["num_consts"]
    n_carry = eqn.params["num_carry"]
    body = eqn.params["jaxpr"]
    consts = ins[:nc]
    carry = list(ins[nc:nc + n_carry])
    xs = ins[nc + n_carry:]
    ys: list[Label] = []
    for _ in range(_FIXPOINT_LIMIT):
        outs = run_jaxpr(body, consts + carry + xs)
        new = [c.join(o) for c, o in zip(carry, outs[:n_carry])]
        ys = outs[n_carry:]
        if new == carry:
            break
        carry = new
    return carry + ys


def _cond(eqn, ins: list[Label]) -> list[Label]:
    pred, ops = ins[0], ins[1:]
    outs: list[Label] | None = None
    for br in eqn.params["branches"]:
        o = run_jaxpr(br, ops)
        outs = o if outs is None else [a.join(b) for a, b in zip(outs, o)]
    capped = pred.cap_bounded()
    return [o.join(capped) for o in (outs or [])] or \
        [capped] * len(eqn.outvars)


# --------------------------------------------------------------------------
# Weiszfeld composite detector
#
# The geometric-median iteration y' = Σ (w_i/d_i(y)) x_i / Σ (w_i/d_i(y))
# is a weighted MEAN at primitive granularity — every eqn on the path is
# join-unbounded — yet its fixed point has bounded per-point influence
# (breakdown 1/2).  The signature, structural and name-free:
#
#   carry-and-raw value → sqrt        (the distance d_i(y))
#   something / sqrt_d                (the inverse weight w_i/d_i)
#   inv_w ⊙ raw_points  (mul or dot)  (the reweighted report sum)
#   … reaching a carry output of the while body.
#
# In a loop that fires, a carry that inv_w reaches with no such product
# on its way is marked ``coef`` for the ``mul``/``dot_general`` rule of
# ``_transfer``: the Gram form carries the coefficients c over X Xᵀ and
# forms y = Σ c_j x_j after the loop, while its movement (c' − c)ᵀG(c' − c)
# is the product that fires.  A loop with no product never fires, so its
# 1/d carries get no mark and a raw carry beside them stays RAW.
#
# Flags union-propagate forward; sub-jaxpr-bearing eqns inside the body
# propagate conservatively (flags joined across the call, no descent).

def _weiszfeld_flags(body, in_labels: list[Label],
                     nconsts: int) -> list[frozenset]:
    """The detector's flags on each carry output of a ``while`` body;
    none where the body cannot be analysed."""
    jaxpr = _closed_parts(body)
    labels: dict[Any, Label] = {}
    try:
        run_jaxpr(jaxpr, in_labels, capture=labels)
    except ValueError:
        return [frozenset()] * len(jaxpr.outvars)

    def lab(v) -> Label:
        if _is_literal(v):
            return CLEAN_LABEL
        return labels.get(v, CLEAN_LABEL)

    flags: dict[Any, frozenset] = {}

    def fl(v) -> frozenset:
        if _is_literal(v):
            return frozenset()
        return flags.get(v, frozenset())

    for i, v in enumerate(jaxpr.invars):
        tag = set()
        if i >= nconsts:
            tag.add("carry")
        if lab(v).level == RAW:
            tag.add("raw")
        flags[v] = frozenset(tag)

    for eqn in jaxpr.eqns:
        out = frozenset()
        for v in eqn.invars:
            out |= fl(v)
        name = eqn.primitive.name
        if name == "sqrt" and eqn.invars:
            f0 = fl(eqn.invars[0])
            if "carry" in f0 and "raw" in f0 and \
                    lab(eqn.invars[0]).level == RAW:
                out |= {"sqrt_d"}
        elif name == "div" and len(eqn.invars) == 2:
            if "sqrt_d" in fl(eqn.invars[1]):
                out |= {"inv_w"}
        elif name in ("mul", "dot_general") and len(eqn.invars) >= 2:
            a, b = eqn.invars[0], eqn.invars[1]
            if ("inv_w" in fl(a) and lab(b).level == RAW) or \
                    ("inv_w" in fl(b) and lab(a).level == RAW):
                out |= {"wprod"}
        for v in eqn.outvars:
            flags[v] = out

    return [fl(v) for v in jaxpr.outvars]
