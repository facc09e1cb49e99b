"""Shared helpers for the benchmark harness."""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro import optim
from repro.core import RobustConfig, make_robust_train_step, theory
from repro.data import regression

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


def ensure_results_dir():
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return RESULTS_DIR


def save_json(name: str, payload):
    ensure_results_dir()
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def save_bench(name: str, payload: dict) -> str:
    """Write a CHECKED-IN benchmark record ``benchmarks/BENCH_<name>.json``.

    Unlike ``save_json`` (scratch output under benchmarks/results/, not
    committed), BENCH files are committed with the PR that produced them so
    reviewers and later sessions can read the performance trajectory from
    git history.  See docs/BENCHMARKS.md for the workflow and field
    conventions.  Metadata records the backend the numbers were taken on:
    a timing measured on CPU says nothing about TPU and vice versa.
    """
    record = {
        "bench": name,
        "recorded_unix": int(time.time()),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "cpu_count": os.cpu_count(),
        **payload,
    }
    path = os.path.join(BENCH_DIR, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=False)
        f.write("\n")
    return path


def run_linreg(*, dim, total_samples, num_workers, num_byzantine,
               num_batches, attack, aggregator, rounds, seed=0,
               rotate=True, trim_multiplier=3.0, eta=None):
    """One Byzantine-GD linreg run; returns per-round error trace
    ||theta_t - theta*||."""
    key = jax.random.PRNGKey(seed)
    ds = regression.generate(key, dim=dim, total_samples=total_samples,
                             num_workers=num_workers)
    rc = RobustConfig(num_workers=num_workers, num_byzantine=num_byzantine,
                      num_batches=num_batches, attack=attack,
                      aggregator=aggregator, rotate_byzantine=rotate,
                      trim_multiplier=trim_multiplier)
    opt = optim.sgd(eta if eta is not None
                    else theory.LINEAR_REGRESSION.step_size)
    step = jax.jit(make_robust_train_step(regression.squared_loss, opt, rc))
    theta = jnp.zeros((dim,))
    opt_state = opt.init(theta)
    batches = regression.worker_batches(ds)
    errs = []
    for t in range(rounds):
        errs.append(float(jnp.linalg.norm(theta - ds.theta_star)))
        theta, opt_state, _ = step(theta, opt_state, batches,
                                   jax.random.fold_in(key, 777), t)
    errs.append(float(jnp.linalg.norm(theta - ds.theta_star)))
    return errs, ds


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.elapsed = time.perf_counter() - self.t0


def time_call(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out   # us per call
