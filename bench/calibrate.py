"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--controls 3]

In one process, so that the cell's program compiles once: for every seed,
the numbers ``correct`` compares for a sound run of the program against
the plain reference; for the first ``--controls`` seeds also the control
(the reference in the next lower precision, put in the program's place)
and the planted faults, each against the same reference.  One JSON line
per reading on standard output.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import run as bench_run  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def lm_step(cj: dict, tr: dict, seeds: list, controls: int) -> None:
    from bench.drivers import lm_step as drv
    cfg, rc, optimizer, params_s = drv.build(cj, tr)
    compiled = None
    for n, seed in enumerate(seeds):
        feed = drv.Feed(cj, tr, seed, params_s)
        if compiled is None:
            compiled = drv.compile_step(cfg, rc, optimizer, params_s, feed)
        t0 = time.perf_counter()
        state, prog = drv.check_steps(drv.make_stepper(compiled, feed),
                                      optimizer, feed, tr)
        del state
        gc.collect()
        t1 = time.perf_counter()
        ref = drv.reference_readings(feed, cj, tr)
        t2 = time.perf_counter()
        _, gaps = drv.compare(prog, ref, tr["limits"])
        emit(seed=seed, kind="program", **gaps,
             program=prog, reference=ref, program_s=t1 - t0,
             reference_s=t2 - t1)
        if n >= controls:
            continue
        for kind, kw in (("control_fp8", {"precision": "fp8"}),
                         ("fault_half_batch", {"half_batch": True})):
            other = drv.reference_readings(feed, cj, tr, **kw)
            _, gaps = drv.compare(other, ref, tr["limits"])
            emit(seed=seed, kind=kind, **gaps, readings=other)


def gd_rounds(cj: dict, tr: dict, seeds: list, controls: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import harness
    from bench.drivers import gd_rounds as drv
    from bench.gen.regression import dataset
    from bench.reference import linreg

    m, q, d = cj["num_workers"], cj["num_byzantine"], cj["dim"]
    runner, opt, _ = drv.build(cj, tr)
    args = drv.reference_args(cj, tr)
    for n, seed in enumerate(seeds):
        key = harness.seed_key(seed)
        x, y, _ = jax.jit(lambda kk: dataset(
            kk, dim=d, total_samples=cj["total_samples"], num_workers=m,
            noise_std=cj["noise_std"]))(jax.random.fold_in(key, 1))
        theta0 = jnp.zeros((d,), jnp.float32)
        gaps, ctl = [], []
        for j in range(tr["sample_jobs"] + 2):
            jk = jax.random.fold_in(jax.random.fold_in(key, 2), j)
            theta = np.asarray(runner(theta0, opt.init(theta0), (x, y), jk,
                                      num_rounds=tr["rounds_per_job"])[0],
                               np.float64)
            masks = linreg.byzantine_masks(jk, tr["rounds_per_job"], m, q)
            ref = linreg.gd_job(x, y, jk, masks=masks, **args)
            gaps.append(float(np.linalg.norm(theta - ref)
                              / np.linalg.norm(ref)))
            if n < controls:
                low = linreg.gd_job(x, y, jk, masks=masks,
                                    dtype="bfloat16", **args)
                ctl.append(float(np.linalg.norm(low - ref)
                                 / np.linalg.norm(ref)))
        del x, y
        emit(seed=seed, kind="program", theta_gap=max(gaps), gaps=gaps)
        if ctl:
            emit(seed=seed, kind="control_bf16", theta_gap=max(ctl),
                 gaps=ctl)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    args = p.parse_args(argv)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, _, cj, tr = bench_run.find_cell(bench, args.workload)
    bench_run.use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    {"lm_step": lm_step, "gd_rounds": gd_rounds}[tr["driver"]](
        cj, tr, seeds, args.controls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
