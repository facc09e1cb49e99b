"""The DeepSeek-V3-style cell's driver at a test size on the CPU: a sound
run is correct, and the fp8 control and each planted fault come out not
correct; and the readers of its expert-layer metrics on a hand-made trace.

The limits here are the test size's own, between what the program reads on
the CPU at this size and what the control reads (six seeds: the program's
first-aggregate gaps 1.6e-3..8.6e-3 and loss gaps 2.8e-4..3.5e-3; the fp8
control's 1.6e-2..2.6e-2 and 2.7e-3..1.2e-2, so at this size the first
aggregate tells them apart and the loss does not).  As in
``test_drivers``, a parameter's change at this size is the rounding of a
few bf16 entries near zero (the program reads 4.6e-2..0.21); a state left
unchanged reads 1.
"""

import copy
import os
import time

import pytest

from bench import harness, scopes
from bench import run as bench_run
from bench import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "bench", "tests", "fixtures")
CELL = "moonlight16b.gmom.signflip"
TINY_LIMITS = {"loss_gap": 5e-3, "first_grad_gap": 1.2e-2, "change_gap": 0.5}


@pytest.fixture(scope="module")
def bench():
    return bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def tiny(bench):
    cell, _, _, traffic = bench_run.find_cell(bench, CELL)
    traffic = copy.deepcopy(traffic)
    traffic.update(seq_len=64, limits=dict(TINY_LIMITS))
    config = bench_run.load_json(os.path.join(FIXTURES, "tiny-mla-moe.json"))
    return cell, config, traffic


def drive(bench, cell, config, traffic, seed=2**31 + 11):
    ctx = harness.Context(workload=cell["name"], config=config,
                          traffic=traffic, seed=seed, seconds=0.3,
                          trace=False, chips=1, t_start=time.perf_counter(),
                          log=lambda *_: None)
    return bench_run.run_cell(ctx, bench, cell, check_device=False)


def test_sound_run_is_correct(bench):
    out = drive(bench, *tiny(bench))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert 0 <= out["readings"]["routing_differs"] < 0.05
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
    real = model_lib.loss_and_stats

    def half(params, batch, cfg):
        t = batch["tokens"].shape[-1] // 2
        return real(params, {k: v[..., :t] for k, v in batch.items()}, cfg)
    monkeypatch.setattr(model_lib, "loss_and_stats", half)


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_fault_is_not_correct(bench, fault, monkeypatch):
    {"unchanged_state": _unchanged_state,
     "half_batch": _half_batch}[fault](monkeypatch)
    out = drive(bench, *tiny(bench))
    assert not out["correct"], out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_control_is_not_correct(bench):
    """The reference in fp8, put in the program's place."""
    from bench.drivers import moe_lm_step as drv
    _, config, traffic = tiny(bench)
    _, _, _, params_s = drv.build(config, traffic)
    feed = drv.Feed(config, traffic, 7, params_s)
    ref = drv.reference_readings(feed, config, traffic)
    low = drv.reference_readings(feed, config, traffic, precision="fp8")
    checks, _ = drv.lm_step.compare(low, ref, traffic["limits"])
    assert any(v > lim for v, lim in checks.values()), checks


def test_expert_leaves_take_their_own_fan_in():
    import jax
    import jax.numpy as jnp
    from bench.drivers import moe_lm_step as drv
    shapes = {"layers": {"moe": {
        "experts": {"w_gate": jax.ShapeDtypeStruct((2, 8, 64, 4096),
                                                   jnp.float32)},
        "router_bias": jax.ShapeDtypeStruct((2, 8), jnp.float32)}}}
    p = drv.init_params(jax.random.PRNGKey(0), shapes)
    w = p["layers"]["moe"]["experts"]["w_gate"]
    assert float(jnp.std(w)) == pytest.approx(0.88 * 64 ** -0.5, rel=0.05)
    assert not jnp.any(p["layers"]["moe"]["router_bias"])


def reader(name):
    return bench_run.load_module(
        os.path.join(ROOT, "bench", "metrics", name + ".py"),
        "m_" + name.replace(".", "_"))


@pytest.fixture
def expert_trace(monkeypatch):
    """1 us window; the grouped matmul kernels carry no scope, as the TPU
    compiler leaves them."""
    ops = [("%fusion.1", 0, 100), ("%fusion.2", 100, 300),
           ("%ragged-dot-none.3 = bf16[8]", 300, 500),
           ("%ragged-dot-metadata.3", 500, 510), ("%fusion.4", 510, 560),
           ("%sort.5", 560, 600), ("%fusion.6", 600, 800)]
    tr = trace_lib.Trace(ops={0: ops}, host=[("bench.window", 0, 1000)])
    base = "jit(train_step)/group_fwd_bwd/while/body"
    prog = scopes.Program(scopes={
        "fusion.1": base + "/mla/dot_general",
        "fusion.2": base + "/mla/concatenate",
        "fusion.4": base + "/experts/mul",
        "sort.5": base + "/dispatch/sort",
        "fusion.6": base + "/router/dot_general"}, loops={}, shared=set())
    monkeypatch.setattr(scopes, "of_reading", lambda r: prog)
    return tr


def test_expert_layer_readers(expert_trace):
    from bench import arith, arith_moe
    cfg = bench_run.load_json(os.path.join(
        ROOT, "bench", "configs", "moonlight-16b-a3b-chip.json"))
    r = trace_lib.Reading(
        trace=expert_trace, device={"kind": "TPU v5 lite", "count": 1},
        counters={"moe_local_assignments": 1000, "moe_expert_calls": 2,
                  "moe_load_max": 1.5, "steps": 1},
        cell={"name": CELL}, config=cfg, traffic={})
    busy = 800.0
    assert reader("step.mla.busy_share").read(r) == \
        pytest.approx(100 * 300 / busy)
    assert reader("step.moe_experts.busy_share").read(r) == \
        pytest.approx(100 * 260 / busy)
    assert reader("step.moe_dispatch.busy_share").read(r) == \
        pytest.approx(100 * 240 / busy)
    t_min, _ = arith.roofline_seconds(
        arith_moe.expert_matmul_flops(cfg, 1000),
        arith_moe.expert_matmul_bytes(cfg, 1000, 2),
        arith.peaks("TPU v5 lite"))
    assert reader("moe_experts_roofline").read(r) == \
        pytest.approx(100 * t_min / 260e-9)
    assert reader("moe.load_max_over_mean").read(r) == 1.5


@pytest.mark.parametrize("name", [
    "step.mla.busy_share", "step.moe_experts.busy_share",
    "step.moe_dispatch.busy_share", "moe_experts_roofline",
    "moe.load_max_over_mean"])
def test_expert_readers_find_nothing_without_their_layers(name, monkeypatch):
    """A dense program (the parent's, or another cell's) names none of the
    expert layer's scopes and counts no assignments."""
    tr = trace_lib.Trace(ops={0: [("%fusion.1", 0, 100)]},
                         host=[("bench.window", 0, 1000)])
    prog = scopes.Program(
        scopes={"fusion.1": "jit(train_step)/group_fwd_bwd/dot_general"},
        loops={}, shared=set())
    monkeypatch.setattr(scopes, "of_reading", lambda r: prog)
    r = trace_lib.Reading(trace=tr, device={"kind": "TPU v5 lite",
                                            "count": 1},
                          counters={"steps": 1}, cell={"name": CELL},
                          config={}, traffic={})
    assert reader(name).read(r) is None
