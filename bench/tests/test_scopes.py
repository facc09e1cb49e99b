"""Device time by the program's named scopes and the readers of the metrics
that use it: on a hand-made trace, and on profiles that JAX writes of the
program at a test size on the CPU."""

import copy
import json
import os
import re
import time

import pytest

from bench import harness, scopes
from bench import run as bench_run
from bench import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "bench", "tests", "fixtures")
NEW_METRICS = ["step.fwd_bwd.busy_share", "step.aggregate.busy_share",
               "step.optimizer.busy_share", "step.weiszfeld.iters",
               "step.weiszfeld.ms_per_iter"]


@pytest.fixture(scope="module")
def scoped():
    with open(os.path.join(FIXTURES, "scoped_trace.json")) as f:
        raw = json.load(f)
    tr = trace_lib.Trace(
        ops={int(c): [tuple(e) for e in evs] for c, evs in raw["ops"].items()},
        host=[tuple(e) for e in raw["host"]])
    return tr, scopes.Program(scopes=raw["scopes"], loops=raw["loops"],
                              shared=set(raw["shared"]))


def reading(tr, name="w", counters=None):
    return trace_lib.Reading(trace=tr, counters=counters or {},
                             device={"kind": "TPU v5 lite", "count": 1},
                             cell={"name": name}, config={}, traffic={})


def reader(name):
    return bench_run.load_module(
        os.path.join(ROOT, "bench", "metrics", name + ".py"),
        "m_" + name.replace(".", "_"))


def test_instruction_of_an_op_event():
    assert scopes.instruction(
        "%while.10 = (s32[], f32[4]{0:T(128)}) while(%tuple.9)") == "while.10"
    assert scopes.instruction("%fusion.3") == "fusion.3"
    assert scopes.instruction("copy-start.3") == "copy-start.3"


def test_scope_seconds_count_a_loop_and_its_body_once(scoped):
    tr, prog = scoped
    assert tr.busy_s() == pytest.approx(1090e-9)
    # the other module's op counts under its namesake's scope: the trace
    # names an op by its instruction alone
    expect = {"group_fwd_bwd": 600e-9, "aggregate": 350e-9,
              "weiszfeld": 310e-9, "trim": 40e-9, "optimizer": 100e-9,
              "attack": 0.0}
    for scope, seconds in expect.items():
        assert scopes.scope_seconds(tr, prog, scope) == pytest.approx(seconds)


def test_loop_iterations_count_the_body_once_an_iteration(scoped):
    tr, prog = scoped
    assert scopes.loop_iterations(tr, prog, "weiszfeld") == 5
    assert scopes.loop_iterations(tr, prog, "group_fwd_bwd") == 2
    assert scopes.loop_iterations(tr, prog, "optimizer") == 0
    # the body's op whose name another module shares counts the other
    # module's run too
    unguarded = scopes.Program(prog.scopes, prog.loops, shared=set())
    assert scopes.loop_iterations(tr, unguarded, "weiszfeld") == 6
    # a loop inside another loop of the scope is no loop of its own
    nested = scopes.Program(prog.scopes, dict(
        prog.loops, **{"fusion.22": ["fusion.21"]}), prog.shared)
    assert scopes.loop_iterations(tr, nested, "weiszfeld") == 5


def test_readers_on_the_hand_made_trace(scoped, monkeypatch):
    tr, prog = scoped
    monkeypatch.setattr(scopes, "of_reading", lambda r: prog)
    r = reading(tr, counters={"steps": 2})
    assert reader("step.fwd_bwd.busy_share").read(r) == \
        pytest.approx(100 * 600 / 1090)
    assert reader("step.aggregate.busy_share").read(r) == \
        pytest.approx(100 * 350 / 1090)
    assert reader("step.optimizer.busy_share").read(r) == \
        pytest.approx(100 * 100 / 1090)
    assert reader("step.weiszfeld.iters").read(r) == 2.5
    assert reader("step.weiszfeld.ms_per_iter").read(r) == \
        pytest.approx(1000 * 310e-9 / 5)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_without_its_program(scoped, name, tmp_path,
                                                  monkeypatch):
    tr, prog = scoped
    # no trace file for the cell
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert reader(name).read(reading(tr)) is None
    # a program that names no scopes, as one before them did
    unnamed = {n: re.sub(r"/(group_fwd_bwd|aggregate|weiszfeld|optimizer|"
                         r"trim)(?=/)", "", p) for n, p in prog.scopes.items()}
    assert scopes.program([scopes.Module(set(unnamed), unnamed,
                                         prog.loops)]) is None
    monkeypatch.setattr(scopes, "of_reading", lambda r: None)
    assert reader(name).read(reading(tr)) is None


def test_fields_of_a_protobuf_message():
    # field 1 varint 150, field 2 bytes b"ab", field 3 packed [1, 300]
    msg = bytes([0x08, 0x96, 0x01, 0x12, 0x02, 0x61, 0x62,
                 0x1a, 0x03, 0x01, 0xac, 0x02])
    got = list(scopes.fields(msg))
    assert got == [(1, 150), (2, b"ab"), (3, b"\x01\xac\x02")]
    assert scopes._ints(got[2][1]) == [1, 300]
    assert scopes._ints(150) == [150]


def test_readers_on_a_v5e_trace(tmp_path, monkeypatch):
    """A traced run of ``mistral7b.gmom.signflip`` on a TPU v5 lite (3 steps,
    seed 3400000011): the readers give what that run printed."""
    import gzip
    from jax.profiler import ProfileData
    cell = "mistral7b.gmom.signflip"
    with gzip.open(os.path.join(FIXTURES, "v5e_gmom_signflip.xplane.pb.gz"),
                   "rb") as f:
        data = f.read()
    (tmp_path / cell).mkdir()
    (tmp_path / cell / "vm.xplane.pb").write_bytes(data)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    tr = trace_lib.from_profile(ProfileData.from_serialized_xspace(data),
                                chips=1)
    r = reading(tr, cell, counters={"steps": 3})
    printed = {"step.fwd_bwd.busy_share": 48.074635633307395,
               "step.aggregate.busy_share": 50.18596879411771,
               "step.optimizer.busy_share": 1.6237658305306686,
               "step.weiszfeld.iters": 32.0,
               "step.weiszfeld.ms_per_iter": 14.272305260416665}
    for name, value in printed.items():
        assert reader(name).read(r) == pytest.approx(value, rel=1e-12)


# ---- profiles JAX writes on the CPU ---------------------------------------

def _profile(fn, args, d):
    """``fn(*args)`` once to compile, then once under the profiler; the
    modules kept in the profile's metadata plane."""
    import glob
    import jax
    jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(str(d))
    jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(d), "**", "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as f:
        return scopes.hlo_modules(f.read())


def _body_of(hlo_text, loop):
    """The instructions of a ``while``'s body computation, from the module's
    text."""
    body = re.search(rf"%{re.escape(loop)} = .*?body=%([\w.\-]+)",
                     hlo_text).group(1)
    block = re.search(rf"^%?{re.escape(body)} .*?\{{\n(.*?)^\}}", hlo_text,
                      re.M | re.S).group(1)
    return re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) =", block, re.M)


def test_the_profile_keeps_the_scoped_group_step(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro import optim
    from repro.configs import get_config
    from repro.core import RobustConfig
    from repro.data.tokens import TokenStream
    from repro.launch import steps
    from repro.models import model as model_lib
    cfg = get_config("minitron-4b").reduced()
    rc = RobustConfig(num_workers=4, num_byzantine=1, num_batches=2,
                      attack="sign_flip", aggregator="gmom")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=8, num_workers=4, seed=0)
    opt = optim.adamw(1e-3)
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    step = jax.jit(steps.make_group_train_step(cfg, rc, opt))
    args = (params, opt.init(params), stream.batch(0),
            jax.random.PRNGKey(5), jnp.int32(0))
    modules = _profile(step, args, tmp_path)
    named = [m for m in modules if scopes.names_scopes(m.scopes)]
    assert len(named) == 1 and len(modules) > 1
    prog = scopes.program(modules)
    assert prog.shared == {n for m in modules if m is not named[0]
                           for n in m.instructions & named[0].instructions}
    seen = {p for path in prog.scopes.values() for p in path.split("/")}
    assert {"group_fwd_bwd", "attack", "aggregate", "batch_means", "trim",
            "weiszfeld", "optimizer", "step_metrics"} <= seen
    text = step.lower(*args).compile().as_text()
    loops = [w for w in prog.loops
             if scopes.in_scope(prog.scopes.get(w, ""), "weiszfeld")]
    assert len(loops) == 1
    assert set(prog.loops[loops[0]]) == set(_body_of(text, loops[0]))


def test_the_profile_keeps_the_scoped_round_runner(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro import optim
    from repro.core import RobustConfig, make_run_rounds
    from repro.data import regression
    ds = regression.generate(jax.random.PRNGKey(1), dim=16,
                             total_samples=400, num_workers=10)
    rc = RobustConfig(num_workers=10, num_byzantine=2, num_batches=5,
                      attack="sign_flip", aggregator="gmom")
    opt = optim.sgd(0.5)
    run = make_run_rounds(regression.squared_loss, opt, rc)
    theta0 = jnp.zeros((16,))
    prog = scopes.program(_profile(
        lambda *a: run(*a, num_rounds=3),
        (theta0, opt.init(theta0), regression.worker_batches(ds),
         jax.random.PRNGKey(2)), tmp_path))
    seen = {p for path in prog.scopes.values() for p in path.split("/")}
    assert {"worker_grads", "attack", "aggregate", "weiszfeld",
            "optimizer", "step_metrics"} <= seen


def test_a_program_without_scopes_reads_none(tmp_path):
    import jax
    import jax.numpy as jnp
    modules = _profile(jax.jit(lambda x: jnp.tanh(x) * 3.0),
                       (jnp.ones((8,)),), tmp_path)
    assert modules and scopes.program(modules) is None


@pytest.fixture(scope="module")
def bench():
    return bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("workload", ["mistral7b.gmom.signflip",
                                      "linreg.paper.gmom"])
def test_traced_run_leaves_the_scoped_program_in_its_profile(
        bench, workload, tmp_path, monkeypatch):
    """The cell's driver, traced, at the drivers' test size on the CPU (the
    trace has no TPU plane there, so the readers themselves are not run):
    the profile the harness keeps holds the program's scoped module."""
    from bench.tests.test_drivers import linreg_cell, tiny_lm
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    cell, config, traffic = (linreg_cell(bench) if workload.startswith(
        "linreg") else tiny_lm(bench, workload))
    ctx = harness.Context(workload=cell["name"], config=config,
                          traffic=copy.deepcopy(traffic), seed=2**33 + 5,
                          seconds=0.3, trace=True, chips=1,
                          t_start=time.perf_counter(), log=lambda *_: None)
    driver = bench_run.load_module(
        os.path.join(ROOT, "bench", "drivers", traffic["driver"] + ".py"),
        "bench_driver_" + traffic["driver"])
    driver.run(ctx)
    prog = scopes.of_reading(reading(None, cell["name"]))
    seen = {p for path in prog.scopes.values() for p in path.split("/")}
    if workload.startswith("linreg"):
        assert {"worker_grads", "aggregate", "weiszfeld"} <= seen
    else:
        assert {"group_fwd_bwd", "aggregate", "weiszfeld",
                "optimizer"} <= seen
        assert any(scopes.in_scope(prog.scopes.get(w, ""), "weiszfeld")
                   for w in prog.loops)
