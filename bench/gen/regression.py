"""The paper's linear-regression data, made on the device from a key.

A copy of the program's generator (``data/regression.generate``) kept with
the benchmark:

    y_i = <w_i, theta*> + zeta_i,  w_i ~ N(0, I_d),  zeta_i ~ N(0, noise^2)

split evenly into the m workers' local sets, (m, N/m, d) features and
(m, N/m) targets, with theta* ~ N(0, I_d).  Each worker's features are
drawn from a key of their own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dataset(key, *, dim: int, total_samples: int, num_workers: int,
            noise_std: float):
    """``(features, targets, theta_star)``."""
    if total_samples % num_workers:
        raise ValueError("the samples must split evenly among the workers")
    per = total_samples // num_workers
    k_theta, k_w, k_z, _ = jax.random.split(key, 4)
    theta_star = jax.random.normal(k_theta, (dim,))
    w = jax.vmap(lambda kw: jax.random.normal(kw, (per, dim)))(
        jax.random.split(k_w, num_workers))
    zeta = noise_std * jax.random.normal(k_z, (num_workers, per))
    y = jnp.einsum("mnd,d->mn", w, theta_star,
                   precision=jax.lax.Precision.HIGHEST) + zeta
    return w, y, theta_star
