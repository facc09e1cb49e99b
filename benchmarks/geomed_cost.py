"""Benchmark 4 — server-side aggregation cost (paper §1.4 / Remark 2).

Claims checked:
  (a) Weiszfeld reaches the (1+gamma)-approximation with gamma = 1/N in few
      iterations (the paper invokes [CLM+16]'s O(qd log^3 N); we substitute
      Weiszfeld and measure its iteration count & wall time).
  (b) cost scales ~ linearly in d and in k (the paper's O(md + qd log^3 N)
      is linear in d at fixed k).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import save_json, time_call
from repro.core.geometric_median import geometric_median, weiszfeld_step


def iterations_to_gamma(points, gamma):
    """# Weiszfeld iterations until objective <= (1+gamma) * best."""
    pts = jnp.asarray(points)
    w = jnp.ones((pts.shape[0],), jnp.float32)

    def obj(y):
        return float(jnp.sum(jnp.linalg.norm(pts - y[None], axis=1)))

    best = obj(np.asarray(geometric_median(pts, max_iters=512, tol=1e-12)))
    y = jnp.mean(pts, axis=0)
    for it in range(1, 200):
        y = weiszfeld_step(pts, y, w, 1e-12)
        if obj(y) <= (1 + gamma) * best + 1e-12:
            return it
    return 200


def main() -> dict:
    rng = np.random.default_rng(0)
    out = {}

    # (a) iterations vs gamma (k=20 batch means, d=1000)
    pts = rng.normal(size=(20, 1000)).astype(np.float32)
    gammas = [1e-2, 1e-4, 1e-6, 1e-8]
    iters = [iterations_to_gamma(pts, g) for g in gammas]
    out["iters_vs_gamma"] = {"gamma": gammas, "iters": iters}
    for g, i in zip(gammas, iters):
        print(f"geomed_cost,gamma={g:.0e},iters={i}")

    # (b) wall time vs d and k (jit'd full geomed, CPU)
    times_d = []
    for d in [100, 1000, 10_000, 100_000]:
        pts = jnp.asarray(rng.normal(size=(20, d)).astype(np.float32))
        fn = jax.jit(lambda p: geometric_median(p, max_iters=32))
        us, _ = time_call(fn, pts, iters=3)
        times_d.append(us)
        print(f"geomed_cost,d={d},us_per_call={us:.0f}")
    out["time_vs_d"] = {"d": [100, 1000, 10_000, 100_000], "us": times_d}

    times_k = []
    for k in [4, 8, 16, 32, 64]:
        pts = jnp.asarray(rng.normal(size=(k, 10_000)).astype(np.float32))
        fn = jax.jit(lambda p: geometric_median(p, max_iters=32))
        us, _ = time_call(fn, pts, iters=3)
        times_k.append(us)
        print(f"geomed_cost,k={k},us_per_call={us:.0f}")
    out["time_vs_k"] = {"k": [4, 8, 16, 32, 64], "us": times_k}

    save_json("geomed_cost.json", out)
    return out


if __name__ == "__main__":
    main()
