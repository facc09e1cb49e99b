"""Learning-rate schedules as pure ``step -> lr`` functions."""

from __future__ import annotations

import jax.numpy as jnp


def constant(lr: float):
    return lambda step: jnp.asarray(lr, jnp.float32)


def linear_warmup(peak_lr: float, *, warmup_steps: int):
    """``peak_lr * min(1, (step + 1) / warmup_steps)``, then constant: the
    first update already moves, by ``peak_lr / warmup_steps``."""
    def sched(step):
        frac = (step.astype(jnp.float32) + 1.0) / max(warmup_steps, 1)
        return peak_lr * jnp.minimum(frac, 1.0)
    return sched


def warmup_cosine(peak_lr: float, *, warmup_steps: int, total_steps: int,
                  final_fraction: float = 0.1):
    def sched(step):
        step = step.astype(jnp.float32)
        warm = peak_lr * step / max(warmup_steps, 1)
        progress = jnp.clip((step - warmup_steps)
                            / max(total_steps - warmup_steps, 1), 0.0, 1.0)
        cos = final_fraction + (1 - final_fraction) * 0.5 * (
            1.0 + jnp.cos(jnp.pi * progress))
        return jnp.where(step < warmup_steps, warm, peak_lr * cos)
    return sched


def inverse_sqrt(peak_lr: float, *, warmup_steps: int = 100):
    def sched(step):
        step = jnp.maximum(step.astype(jnp.float32), 1.0)
        return peak_lr * jnp.minimum(
            step / max(warmup_steps, 1),
            jnp.sqrt(warmup_steps / step))
    return sched
