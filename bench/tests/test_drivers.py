"""The drivers at a test size on the CPU: a sound run is correct, and the
control and each planted fault of the timed path come out not correct.

The harness's look for a chip is skipped (``check_device=False``); all else
runs as on the chip.  The LM limits here are the test size's own: they sit
between what the program reads on the CPU at this size and what the
control reads.  At the test size a parameter's change after three warmed-up
steps is the rounding of a few bf16 entries near zero, so its gap reads
about 1e-2 on sound runs; a state left unchanged reads 1.
"""

import copy
import os
import time

import pytest

from bench import harness
from bench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "bench", "tests", "fixtures")
# test-size readings on the CPU (both cells, six seeds each): the program
# reads loss gaps of 1.6e-4..9.9e-4 and first-aggregate gaps of
# 5.1e-4..3.6e-3; the fp8 control 1.9e-3..1.3e-2 and 7.2e-3..2.2e-2.
TINY_LM_LIMITS = {"loss_gap": 1.4e-3, "first_grad_gap": 5e-3,
                  "change_gap": 0.5}


@pytest.fixture(scope="module")
def bench():
    return bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def tiny_lm(bench, workload):
    cell, _, _, traffic = bench_run.find_cell(bench, workload)
    traffic = copy.deepcopy(traffic)
    traffic.update(seq_len=64, limits=dict(TINY_LM_LIMITS))
    config = bench_run.load_json(os.path.join(FIXTURES, "tiny-dense.json"))
    return cell, config, traffic


def drive(bench, cell, config, traffic, seed=2**31 + 11):
    ctx = harness.Context(workload=cell["name"], config=config,
                          traffic=traffic, seed=seed, seconds=0.3,
                          trace=False, chips=1, t_start=time.perf_counter(),
                          log=lambda *_: None)
    return bench_run.run_cell(ctx, bench, cell, check_device=False)


def fails_a_check(out):
    return any(c["value"] > c["limit"] for c in out["checks"].values())


LM_CELLS = ["mistral7b.gmom.signflip", "mistral7b.mean.k1"]


@pytest.mark.parametrize("workload", LM_CELLS)
def test_lm_sound_run_is_correct(bench, workload):
    out = drive(bench, *tiny_lm(bench, workload))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def _unchanged_state(monkeypatch):
    from repro.launch import steps
    real = steps.make_group_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def frozen(params, opt_state, *rest):
            _, _, metrics = step(params, opt_state, *rest)
            return params, opt_state, metrics
        return frozen
    monkeypatch.setattr(steps, "make_group_train_step", make)


def _half_batch(monkeypatch):
    from repro.models import model as model_lib
    real = model_lib.loss_fn

    def half(params, batch, cfg):
        t = batch["tokens"].shape[-1] // 2
        return real(params, {k: v[..., :t] for k, v in batch.items()}, cfg)
    monkeypatch.setattr(model_lib, "loss_fn", half)


@pytest.mark.parametrize("workload", LM_CELLS)
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_lm_fault_is_not_correct(bench, workload, fault, monkeypatch):
    {"unchanged_state": _unchanged_state,
     "half_batch": _half_batch}[fault](monkeypatch)
    out = drive(bench, *tiny_lm(bench, workload))
    assert not out["correct"] and fails_a_check(out), out["checks"]


@pytest.mark.parametrize("workload", LM_CELLS)
def test_lm_control_is_not_correct(bench, workload):
    """The reference in fp8, put in the program's place."""
    from bench.drivers import lm_step as drv
    _, config, traffic = tiny_lm(bench, workload)
    _, _, _, params_s = drv.build(config, traffic)
    feed = drv.Feed(config, traffic, 7, params_s)
    ref = drv.reference_readings(feed, config, traffic)
    low = drv.reference_readings(feed, config, traffic, precision="fp8")
    checks, _ = drv.compare(low, ref, traffic["limits"])
    assert any(v > lim for v, lim in checks.values()), checks


def test_lm_number_without_limit_is_read_not_compared(bench):
    cell, config, traffic = tiny_lm(bench, LM_CELLS[0])
    traffic["limits"]["loss_gap"] = None
    out = drive(bench, cell, config, traffic)
    assert "loss_gap" not in out["checks"]
    assert out["readings"]["gaps"]["loss_gap"] >= 0
    assert set(out["checks"]) == {"first_grad_gap", "change_gap"}


LINREG = "linreg.paper.gmom"


def linreg_cell(bench):
    cell, _, config, traffic = bench_run.find_cell(bench, LINREG)
    config = dict(config, dim=100, total_samples=50_000)
    traffic = copy.deepcopy(traffic)
    return cell, config, traffic


def test_linreg_sound_run_is_correct(bench):
    out = drive(bench, *linreg_cell(bench))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"gd_rounds_per_s", "setup_s"}


def _runner_wrapping(monkeypatch, alter):
    import repro.core as core
    real = core.make_run_rounds

    def make(*a, **kw):
        run = real(*a, **kw)

        def wrapped(params, *rest, **kw2):
            out = run(params, *rest, **kw2)
            return (alter(params, out[0]),) + tuple(out[1:])
        return wrapped
    monkeypatch.setattr(core, "make_run_rounds", make)


def _linreg_half_batch(monkeypatch):
    from repro.data import regression
    real = regression.squared_loss

    def half(theta, batch):
        w, y = batch
        n = y.shape[-1] // 2
        return real(theta, (w[..., :n, :], y[..., :n]))
    monkeypatch.setattr(regression, "squared_loss", half)


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_linreg_fault_is_not_correct(bench, fault, monkeypatch):
    if fault == "unchanged_state":
        _runner_wrapping(monkeypatch, lambda theta0, theta: theta0)
    elif fault == "altered_answer":
        _runner_wrapping(monkeypatch,
                         lambda theta0, theta: theta.at[0].add(0.01))
    else:
        _linreg_half_batch(monkeypatch)
    out = drive(bench, *linreg_cell(bench))
    assert not out["correct"] and fails_a_check(out), out["checks"]


def test_linreg_control_is_not_correct(bench):
    """The reference in bfloat16, put in the program's place."""
    import jax
    import numpy as np
    from bench.gen.regression import dataset
    from bench.reference import linreg
    _, config, traffic = linreg_cell(bench)
    rob = traffic["robust"]
    x, y, _ = dataset(jax.random.PRNGKey(3), dim=config["dim"],
                      total_samples=config["total_samples"],
                      num_workers=config["num_workers"],
                      noise_std=config["noise_std"])
    args = dict(rounds=traffic["rounds_per_job"],
                byzantine=config["num_byzantine"],
                batches=config["num_batches"],
                attack_scale=rob["attack_scale"],
                step_size=traffic["step_size"],
                trim_multiplier=rob["trim_multiplier"],
                max_iters=rob["max_iters"], tol=rob["tol"])
    key = jax.random.PRNGKey(4)
    ref = linreg.gd_job(x, y, key, **args)
    low = linreg.gd_job(x, y, key, dtype="bfloat16", **args)
    gap = np.linalg.norm(low - ref) / np.linalg.norm(ref)
    assert gap > traffic["limits"]["theta_gap"], gap
