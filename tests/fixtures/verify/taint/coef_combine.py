"""Dummy aggregator for the Layer C taint tests: the Gram-form combine with
coefficients that no Weiszfeld loop produced.

It weights each report by the inverse of its distance to the plain mean,
normalized — one reweighting computed in closed form — and combines the
reports with the very ``weighted_sum`` the geometric median's combine
uses.  No loop carries these coefficients, so the combine must certify
RAW.  Importing this module registers ``_coef_combine``; call
:func:`unregister` in a ``finally`` block.
"""

import jax
import jax.numpy as jnp

from repro.core import aggregators
from repro.core.geometric_median import weighted_sum

NAME = "_coef_combine"


@aggregators.register(
    NAME,
    "test-only: inverse-distance coefficients without a Weiszfeld loop, "
    "combined as the Gram-form median combines its coefficients",
    shard_contract="norm_based")
def _coef_combine_aggregator(stacked_grads, **_kw):
    leaves, treedef = jax.tree.flatten(stacked_grads)
    mean = [jnp.mean(l.astype(jnp.float32), axis=0) for l in leaves]
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32) - m),
                     axis=tuple(range(1, l.ndim)))
             for l, m in zip(leaves, mean))
    inv = 1.0 / jnp.sqrt(sq + 1e-24)
    c = inv / jnp.sum(inv)
    return jax.tree.unflatten(treedef, [weighted_sum(c, l) for l in leaves])


def unregister():
    aggregators._REGISTRY.pop(NAME, None)
