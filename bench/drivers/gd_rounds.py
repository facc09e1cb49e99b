"""Driver: repeated Byzantine GD estimation jobs on the paper's linear
regression, through the program's scanned multi-round runner.

Set-up makes one dataset on the device from the seed, builds the runner
(``core.robust_train.make_run_rounds`` with the configured attack schedule,
aggregator and SGD) and runs one job to compile it.  The window then
dispatches jobs back to back, each ``rounds_per_job`` rounds from theta = 0
as one scanned call with its own key, keeping one job in flight ahead of
the host.  Once the window has closed, a sample of the window's jobs drawn
from the seed (the first and the last among them) is compared with the
plain reference (``bench/reference/linreg.py``) run on the same data and
keys: the gap is the largest ||theta - theta_ref|| / ||theta_ref||.
Job j's key is ``fold_in(job key, j)``.

Traffic keys: ``rounds_per_job``, ``robust`` (aggregator, attack,
attack_scale, schedule, trim_multiplier, max_iters, tol, round_backend),
``step_size``, ``sample_jobs``, ``trace_seconds`` and ``limits``.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench import arith, harness
from bench.gen.regression import dataset
from bench.reference import linreg


def build(cj: dict, tr: dict):
    """The program's multi-round runner for this configuration and
    traffic: ``(runner, optimizer, resolved round backend)``."""
    from repro import optim
    from repro.core import RobustConfig, aggregators, byzantine
    from repro.core import make_run_rounds
    from repro.data import regression

    rob = tr["robust"]
    m, q, k, d = (cj["num_workers"], cj["num_byzantine"], cj["num_batches"],
                  cj["dim"])
    attack_kwargs = (("scale", rob["attack_scale"]),)
    rc = RobustConfig(num_workers=m, num_byzantine=q, num_batches=k,
                      aggregator=rob["aggregator"], attack=rob["attack"],
                      attack_kwargs=attack_kwargs,
                      trim_multiplier=rob["trim_multiplier"],
                      gmom_max_iters=rob["max_iters"], gmom_tol=rob["tol"],
                      round_backend=rob["round_backend"])
    backend = aggregators.resolve_round_backend(
        rob["round_backend"], num_batches=k, total_dim=d, num_workers=m)
    schedule = byzantine.make_schedule(
        rob["schedule"], num_workers=m, num_byzantine=q, attack=rob["attack"],
        attack_kwargs=attack_kwargs)
    opt = optim.sgd(tr["step_size"])
    runner = make_run_rounds(regression.squared_loss, opt, rc,
                             schedule=schedule)
    return runner, opt, backend


def reference_args(cj: dict, tr: dict) -> dict:
    """``bench/reference/linreg.gd_job``'s keyword arguments."""
    rob = tr["robust"]
    return dict(rounds=tr["rounds_per_job"], byzantine=cj["num_byzantine"],
                batches=cj["num_batches"], attack_scale=rob["attack_scale"],
                step_size=tr["step_size"],
                trim_multiplier=rob["trim_multiplier"],
                max_iters=rob["max_iters"], tol=rob["tol"])


def run(ctx: harness.Context) -> dict:
    import jax
    import jax.numpy as jnp

    cj, tr = ctx.config, ctx.traffic
    m, d = cj["num_workers"], cj["dim"]
    rounds = tr["rounds_per_job"]
    runner, opt, backend = build(cj, tr)

    key = harness.seed_key(ctx.seed)
    k_data, k_jobs = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    with harness.span("bench.init"):
        x, y, _ = jax.jit(lambda kk: dataset(
            kk, dim=d, total_samples=cj["total_samples"], num_workers=m,
            noise_std=cj["noise_std"]))(k_data)
        theta0 = jnp.zeros((d,), jnp.float32)
        opt0 = opt.init(theta0)

    job_key = jax.jit(jax.random.fold_in)

    def job(j):
        with harness.span("bench.dispatch"):
            return runner(theta0, opt0, (x, y), job_key(k_jobs, j),
                          num_rounds=rounds)[0]

    with harness.span("bench.compile"):
        jax.block_until_ready(job(0))
    setup_s = ctx.elapsed()
    ctx.log(f"[gd_rounds] set-up {setup_s:.2f} s, round backend {backend}")

    thetas = []
    limit_s = tr["trace_seconds"] if ctx.trace else ctx.seconds
    with harness.traced(ctx) as trace:
        t0 = time.perf_counter()
        prev = None
        while True:
            theta = job(len(thetas))
            thetas.append(theta)
            if prev is not None:
                with harness.span("bench.wait"):
                    prev.block_until_ready()
            prev = theta
            if time.perf_counter() - t0 >= limit_s:
                break
        with harness.span("bench.wait"):
            prev.block_until_ready()
        window_s = time.perf_counter() - t0
    n = len(thetas)
    peak = harness.memory_peak_bytes()
    ctx.log(f"[gd_rounds] window {window_s:.3f} s, {n} jobs")

    rng = np.random.default_rng(ctx.seed)
    sample = sorted({0, n - 1} | set(
        rng.choice(n, size=min(n, tr["sample_jobs"]), replace=False).tolist()))
    prog = {j: np.asarray(thetas[j], np.float64) for j in sample}
    failed = sum(not np.all(np.isfinite(t)) for t in prog.values())
    del thetas, prev, theta
    t_ref = time.perf_counter()
    gaps = []
    for j in sample:
        ref = linreg.gd_job(x, y, job_key(k_jobs, j),
                            **reference_args(cj, tr))
        gaps.append(float(np.linalg.norm(prog[j] - ref)
                          / np.linalg.norm(ref)))
    ctx.log(f"[gd_rounds] reference {time.perf_counter() - t_ref:.1f} s "
            f"over jobs {sample}")
    gap = max(gaps)
    if not math.isfinite(gap):
        gap = math.inf
    total = cj["total_samples"]
    return {
        "setup_s": setup_s, "attempted": n, "failed": failed,
        "e2e": {"gd_rounds_per_s": n * rounds / window_s},
        "checks": {"theta_gap": (gap, tr["limits"]["theta_gap"])},
        "memory_peak_bytes": peak, "trace_path": trace["path"],
        "counters": {"rounds": n * rounds, "jobs": n, "window_s": window_s,
                     "round_backend": backend,
                     "round_bytes": arith.gd_round_bytes(m, d),
                     "round_kernel_flops": arith.gd_round_kernel_flops(m, d),
                     "round_flops": arith.linreg_round_flops(total, d)},
        "readings": {"theta_gaps": gaps, "sample_jobs": sample},
    }
