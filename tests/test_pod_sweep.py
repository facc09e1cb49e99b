"""Multi-pod scenario sweep engine (repro.sim.sweep) + the CI contract.

Tier-1-fast coverage:
* registry shape: the full attack × schedule × aggregator matrix exists on
  both production meshes, names are well-formed, lookups work;
* the sweep record schema round-trips through JSON and self-compares clean;
* the --check gate flags an injected collective-bytes regression, a missing
  scenario, and a stale record entry (library + CLI exit codes);
* one PodScenario lowers end-to-end on a small host-device mesh (subprocess:
  the virtual-device flag must precede jax init) and produces a schema-valid
  entry with nonzero collectives;
* .github/workflows/ci.yml parses and wires the two lanes the README
  documents (tier1 on push/PR; nightly slow lane running the sweep gate).
"""

import copy
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.roofline import analysis
from repro.sim import sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_entry(name: str, *, coll=1.0e9, peak=2.0e9) -> dict:
    rec = analysis.RooflineRecord(
        arch="minitron-4b", shape="train_4k", mesh="16x16",
        step="train_step", flops_per_device=1e12, bytes_per_device=1e12,
        collective_bytes_per_device=coll,
        collective_breakdown={"all-gather": coll * 0.5,
                              "all-reduce": coll * 0.5},
        peak_memory_bytes=peak, model_flops_global=1e15, num_chips=256)
    entry = analysis.sweep_entry(rec, scenario=name)
    entry.update(aggregator="gmom", attack="sign_flip", schedule="static",
                 round_backend="auto", num_groups=4, num_byzantine=1,
                 compile_seconds=1.0)
    return entry


def _fake_payload(names, **kw) -> dict:
    return {"matrix": {"attacks": list(sweep.POD_ATTACKS),
                       "schedules": list(sweep.POD_SCHEDULES),
                       "aggregators": list(sweep.POD_AGGREGATORS),
                       "meshes": list(sweep.POD_MESHES)},
            "scenarios": {n: _fake_entry(n, **kw) for n in names}}


# ---------------------------------------------------------------------------
# registry

def test_registry_covers_full_matrix_on_both_meshes():
    names = sweep.available()
    expected = (len(sweep.POD_ATTACKS) * len(sweep.POD_SCHEDULES)
                * len(sweep.POD_AGGREGATORS) * len(sweep.POD_MESHES)
                + len(sweep.BIG_MODEL_SCENARIOS)
                + len(sweep.COMPRESSION_SCENARIOS)
                + len(sweep.STALE_SCENARIOS))
    assert len(names) == expected
    for mesh in sweep.POD_MESHES:
        for agg in sweep.POD_AGGREGATORS:
            for attack in sweep.POD_ATTACKS:
                for schedule in sweep.POD_SCHEDULES:
                    name = (f"pod/{mesh}/{sweep.DEFAULT_ARCH}/{agg}/"
                            f"{attack}/{schedule}")
                    ps = sweep.get_pod_scenario(name)
                    assert (ps.mesh, ps.aggregator, ps.attack, ps.schedule) \
                        == (mesh, agg, attack, schedule)


def test_big_model_cells_registered():
    """The qwen2-72b shard-scaling cells: sharded gmom/krum/coord_median
    plus the gathered-baseline gmom twin."""
    for name in sweep.BIG_MODEL_SCENARIOS:
        ps = sweep.get_pod_scenario(name)
        assert ps.arch == sweep.BIG_MODEL_ARCH
        assert ps.mesh == "16x16"
        expect = "gathered" if name.endswith("/gathered") else "sharded"
        assert ps.grad_mode == expect, name
    gathered = [n for n in sweep.BIG_MODEL_SCENARIOS
                if sweep.get_pod_scenario(n).grad_mode == "gathered"]
    assert len(gathered) == 1
    assert sweep.get_pod_scenario(gathered[0]).aggregator == "gmom"
    aggs = {sweep.get_pod_scenario(n).aggregator
            for n in sweep.BIG_MODEL_SCENARIOS}
    assert {"gmom", "krum", "coord_median"} <= aggs


def test_registry_rejects_unknown_and_duplicate():
    with pytest.raises(KeyError, match="unknown pod scenario"):
        sweep.get_pod_scenario("pod/nope")
    existing = sweep.get_pod_scenario(sweep.available()[0])
    with pytest.raises(ValueError, match="already registered"):
        sweep.register(existing)
    with pytest.raises(ValueError, match="unknown mesh"):
        sweep.register(sweep.PodScenario(name="pod/bad-mesh", mesh="3x3"))


def test_pod_scenario_builds_rc_and_schedule():
    ps = sweep.get_pod_scenario(
        f"pod/2x16x16/{sweep.DEFAULT_ARCH}/gmom/alie/stealth_then_strike")
    rc = ps.robust_config()
    assert rc.aggregator == "gmom" and rc.attack == "alie"
    assert rc.num_workers == rc.num_batches == ps.num_groups
    sched = ps.build_schedule()
    assert sched.name == "stealth_then_strike"
    assert sched.num_workers == ps.num_groups


# ---------------------------------------------------------------------------
# record schema + gate

def test_sweep_entry_schema_roundtrips_and_self_compares_clean():
    payload = _fake_payload(sweep.available()[:3])
    rt = json.loads(json.dumps(payload))
    assert rt == payload
    problems, notes = sweep.compare_payloads(rt, payload)
    assert problems == [] and notes == []


def test_check_flags_injected_collective_regression():
    names = sweep.available()[:2]
    record = _fake_payload(names)
    fresh = copy.deepcopy(record)
    fresh["scenarios"][names[0]]["collective_bytes_per_device"] *= 1.5
    problems, _ = sweep.compare_payloads(record, fresh)
    assert len(problems) == 1
    assert names[0] in problems[0] and "collective bytes regressed" \
        in problems[0]


def test_check_flags_memory_regression_and_improvement_note():
    names = sweep.available()[:1]
    record = _fake_payload(names)
    fresh = copy.deepcopy(record)
    fresh["scenarios"][names[0]]["peak_memory_bytes"] *= 2.0
    fresh["scenarios"][names[0]]["collective_bytes_per_device"] *= 0.5
    problems, notes = sweep.compare_payloads(record, fresh)
    assert len(problems) == 1 and "peak memory regressed" in problems[0]
    assert any("improved" in n for n in notes)


def test_check_flags_missing_and_stale_scenarios():
    names = sweep.available()[:2]
    record = _fake_payload(names[:1])
    fresh = _fake_payload(names[1:])
    problems, _ = sweep.compare_payloads(record, fresh)
    assert any("not in the checked-in record" in p for p in problems)
    assert any("stale record entry" in p for p in problems)


def test_small_drift_within_tolerance_passes():
    names = sweep.available()[:1]
    record = _fake_payload(names)
    fresh = copy.deepcopy(record)
    fresh["scenarios"][names[0]]["collective_bytes_per_device"] *= 1.01
    fresh["scenarios"][names[0]]["peak_memory_bytes"] *= 1.05
    problems, _ = sweep.compare_payloads(record, fresh)
    assert problems == []


def _fake_big_model_payload(*, gmom_peak=1.0e10, gathered_peak=None,
                            krum_peak=None) -> dict:
    base = f"pod/16x16/{sweep.BIG_MODEL_ARCH}/gmom/sign_flip/static"
    krum = f"pod/16x16/{sweep.BIG_MODEL_ARCH}/krum/sign_flip/static"
    if gathered_peak is None:
        gathered_peak = gmom_peak * sweep.SHARD_MEMORY_MIN_RATIO * 2
    if krum_peak is None:
        krum_peak = gmom_peak * 1.1
    scenarios = {
        base: _fake_entry(base, peak=gmom_peak),
        base + "/gathered": _fake_entry(base + "/gathered",
                                        peak=gathered_peak),
        krum: _fake_entry(krum, peak=krum_peak),
    }
    scenarios[base + "/gathered"]["grad_mode"] = "gathered"
    return {"scenarios": scenarios}


def test_shard_scaling_gate_passes_on_clean_ratios():
    payload = _fake_big_model_payload()
    assert sweep.shard_scaling_problems(payload["scenarios"]) == []


def test_shard_scaling_gate_flags_lost_memory_ratio():
    payload = _fake_big_model_payload(
        gmom_peak=1.0e10,
        gathered_peak=1.0e10 * (sweep.SHARD_MEMORY_MIN_RATIO - 1))
    problems = sweep.shard_scaling_problems(payload["scenarios"])
    assert len(problems) == 1
    assert "O(d/shards)" in problems[0]


def test_shard_scaling_gate_flags_krum_blowup():
    payload = _fake_big_model_payload(
        gmom_peak=1.0e10,
        krum_peak=1.0e10 * (sweep.KRUM_PEAK_MAX_RATIO + 1))
    problems = sweep.shard_scaling_problems(payload["scenarios"])
    assert len(problems) == 1
    assert "krum" in problems[0]


def test_compression_cells_registered():
    """The §1.4 wire-cost cells: two full-step compressed aggregation cells
    plus the three report-wire microcells (f32 baseline / sign / int8)."""
    for name in sweep.COMPRESSION_SCENARIOS:
        ps = sweep.get_pod_scenario(name)
        assert ps.mesh == "16x16" and ps.arch == sweep.DEFAULT_ARCH
        assert ps.wire == name.endswith("/wire"), name
        assert ps.robust_config().compression == ps.compression
    wire = {sweep.get_pod_scenario(n).compression
            for n in sweep.COMPRESSION_SCENARIOS if n.endswith("/wire")}
    assert wire == {"none", "sign", "int8_stochastic"}
    full = {(sweep.get_pod_scenario(n).aggregator,
             sweep.get_pod_scenario(n).attack)
            for n in sweep.COMPRESSION_SCENARIOS if not n.endswith("/wire")}
    assert ("sign_sgd_majority", "sign_flip_targeted") in full
    assert ("int8_gmom", "sign_flip") in full


def _fake_wire_payload(*, f32=8.0e10, sign=None, int8=None) -> dict:
    if sign is None:
        sign = f32 / 32.0
    if int8 is None:
        int8 = f32 / 4.0
    return {sweep.WIRE_BASELINE_SCENARIO:
            _fake_entry(sweep.WIRE_BASELINE_SCENARIO, coll=f32),
            sweep.WIRE_SIGN_SCENARIO:
            _fake_entry(sweep.WIRE_SIGN_SCENARIO, coll=sign),
            sweep.WIRE_INT8_SCENARIO:
            _fake_entry(sweep.WIRE_INT8_SCENARIO, coll=int8)}


def test_wire_gate_passes_on_clean_ratios():
    assert sweep.compression_wire_problems(_fake_wire_payload()) == []


def test_wire_gate_flags_lost_sign_reduction():
    scenarios = _fake_wire_payload(f32=8.0e10, sign=8.0e10 / 20.0)
    problems = sweep.compression_wire_problems(scenarios)
    assert len(problems) == 1
    assert "sign" in problems[0] and "wire-cost claim" in problems[0]


def test_wire_gate_flags_lost_int8_reduction():
    scenarios = _fake_wire_payload(f32=8.0e10, int8=8.0e10 / 2.0)
    problems = sweep.compression_wire_problems(scenarios)
    assert len(problems) == 1 and "int8" in problems[0]


def test_wire_gate_tolerates_rtol_and_flags_optimized_away_wire():
    # just inside the 5% slack of the 25x floor: no problem
    ok = _fake_wire_payload(f32=8.0e10,
                            sign=8.0e10 / (25.0 * (1 - 0.04)))
    assert sweep.compression_wire_problems(ok) == []
    gone = _fake_wire_payload()
    gone[sweep.WIRE_SIGN_SCENARIO]["collective_bytes_per_device"] = 0.0
    problems = sweep.compression_wire_problems(gone)
    assert len(problems) == 1 and "optimized away" in problems[0]


def test_wire_gate_skips_absent_cells():
    assert sweep.compression_wire_problems({}) == []
    base_only = {sweep.WIRE_BASELINE_SCENARIO:
                 _fake_entry(sweep.WIRE_BASELINE_SCENARIO)}
    assert sweep.compression_wire_problems(base_only) == []


def test_shard_scaling_gate_skips_absent_cells():
    """Filtered --check runs / --fresh-from subsets without the big-model
    cells must not trip the gate."""
    names = sweep.available()[:2]
    payload = _fake_payload(names)
    assert sweep.shard_scaling_problems(payload["scenarios"]) == []
    assert sweep.shard_scaling_problems({}) == []


def test_cli_check_exit_codes(tmp_path):
    """sweep --check wiring: clean record -> 0, doctored regression -> 1,
    no record -> 2 (uses --fresh-from so no lowering happens)."""
    names = sweep.available()[:2]
    fresh = _fake_payload(names)
    fresh_path = tmp_path / "fresh.json"
    fresh_path.write_text(json.dumps(fresh))

    ok_record = tmp_path / "record_ok.json"
    ok_record.write_text(json.dumps(fresh))
    bad = copy.deepcopy(fresh)
    bad["scenarios"][names[0]]["collective_bytes_per_device"] *= 0.5
    bad_record = tmp_path / "record_bad.json"
    bad_record.write_text(json.dumps(bad))

    def run(record_path):
        return subprocess.run(
            [sys.executable, "-m", "repro.sim.sweep", "--check",
             "--fresh-from", str(fresh_path),
             "--record-path", str(record_path)],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))

    res = run(ok_record)
    assert res.returncode == 0, (res.stdout, res.stderr[-2000:])
    res = run(bad_record)
    assert res.returncode == 1 and "REGRESSION" in res.stdout, \
        (res.stdout, res.stderr[-2000:])
    res = run(tmp_path / "missing.json")
    assert res.returncode == 2, (res.stdout, res.stderr[-2000:])


def test_cli_filtered_check_ignores_out_of_scope_record_entries(tmp_path):
    """--check --single-pod against the full-matrix record must not call
    the unswept 2x16x16 entries stale (exit 0)."""
    single = [n for n in sweep.available()
              if sweep.get_pod_scenario(n).mesh == "16x16"][:2]
    multi = [n for n in sweep.available()
             if sweep.get_pod_scenario(n).mesh == "2x16x16"][:2]
    record_path = tmp_path / "record.json"
    record_path.write_text(json.dumps(_fake_payload(single + multi)))
    fresh_path = tmp_path / "fresh.json"
    fresh_path.write_text(json.dumps(_fake_payload(single)))
    res = subprocess.run(
        [sys.executable, "-m", "repro.sim.sweep", "--check", "--single-pod",
         "--scenario", single[0], "--scenario", single[1],
         "--fresh-from", str(fresh_path), "--record-path", str(record_path)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout, res.stderr[-2000:])
    assert "stale" not in res.stdout


def test_force_host_device_count_upgrades_stale_flag():
    """A pre-exported smaller device-count flag is raised in place (the old
    import-time mutation silently kept the stale value)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_allow_excess_precision " \\
            "--xla_force_host_platform_device_count=8"
        from repro.launch import dryrun
        dryrun.force_host_device_count(64)
        flags = os.environ["XLA_FLAGS"]
        assert "--xla_force_host_platform_device_count=64" in flags, flags
        assert "--xla_allow_excess_precision" in flags, flags
        import jax
        assert jax.device_count() == 64, jax.device_count()
        dryrun.force_host_device_count(32)   # enough devices: no-op
        print("OK")
    """)
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-3000:])
    assert "OK" in res.stdout


def test_checked_in_record_covers_registry():
    """The committed BENCH_pod_sweeps.json covers every registered scenario
    and both meshes (check_docs enforces the same invariant in CI)."""
    assert os.path.exists(sweep.BENCH_PATH), \
        "benchmarks/BENCH_pod_sweeps.json missing — run " \
        "`python -m repro.sim.sweep --all` and commit it"
    rec = sweep.load_record()
    scenarios = rec.get("scenarios", {})
    missing = [n for n in sweep.available() if n not in scenarios]
    assert not missing, f"record missing scenarios: {missing[:5]} ..."
    recorded_meshes = {e["mesh"] for e in scenarios.values()}
    assert set(sweep.POD_MESHES) <= recorded_meshes, recorded_meshes
    for name, entry in scenarios.items():
        assert entry["collective_bytes_per_device"] > 0
        ps = sweep.get_pod_scenario(name)
        expect = ("report_wire" if ps.wire
                  else "stale_report" if ps.stale else "train_step")
        assert entry["step"] == expect, name


# ---------------------------------------------------------------------------
# one real lowering on a small host-device mesh (subprocess: the virtual
# device flag must be set before jax initializes)

_LOWER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    from repro.configs import get_config
    from repro.configs.base import InputShape
    import repro.configs.shapes as shapes_mod
    from repro.launch import mesh as mesh_lib
    from repro.sim import sweep

    small = InputShape("train_tiny", seq_len=32, global_batch=16,
                       kind="train")
    shapes_mod.SHAPES[small.name] = small
    ps = sweep.get_pod_scenario(
        "pod/2x16x16/%s/gmom/alie/stealth_then_strike" % sweep.DEFAULT_ARCH)
    entry = sweep.lower_scenario(
        ps, mesh=mesh_lib.make_debug_mesh(data=2, model=2, pod=2),
        cfg=get_config(sweep.DEFAULT_ARCH).reduced(), shape="train_tiny")
    assert entry["scenario"] == ps.name
    assert entry["num_chips"] == 8
    assert entry["collective_bytes_per_device"] > 0
    assert set(entry["collective_breakdown"]) == {
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute"}
    json.dumps(entry)   # JSON-stable
    print("OK", int(entry["collective_bytes_per_device"]))
""")


def test_pod_scenario_lowers_on_small_mesh():
    res = subprocess.run(
        [sys.executable, "-c", _LOWER_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-1000:], res.stderr[-3000:])
    assert "OK" in res.stdout


# ---------------------------------------------------------------------------
# CI workflow contract

def test_ci_workflow_parses_and_wires_both_lanes():
    yaml = pytest.importorskip("yaml")
    path = os.path.join(REPO, ".github", "workflows", "ci.yml")
    assert os.path.exists(path), ".github/workflows/ci.yml missing"
    with open(path) as f:
        wf = yaml.safe_load(f)
    # pyyaml parses the bare `on:` key as boolean True
    triggers = wf.get("on", wf.get(True))
    assert "pull_request" in triggers and "push" in triggers
    assert "schedule" in triggers and "workflow_dispatch" in triggers

    jobs = wf["jobs"]
    assert set(jobs) == {"tier1", "slow"}
    tier1_text = json.dumps(jobs["tier1"])
    assert "python -m pytest -x -q" in tier1_text
    assert "scripts/check_docs.py" in tier1_text
    assert "repro.sim.goldens --check" in tier1_text
    # every matrix entry pins the jax the repo runs on (no floor shims)
    import jax
    matrix = jobs["tier1"]["strategy"]["matrix"]["include"]
    assert matrix and all(m["jax-version"] == jax.__version__
                          for m in matrix)
    assert any(step.get("with", {}).get("cache") == "pip"
               for step in jobs["tier1"]["steps"] if isinstance(step, dict))

    slow_text = json.dumps(jobs["slow"])
    assert "repro.sim.sweep --check" in slow_text
    assert '-m pytest -q -m' in slow_text
    # slow lane only fires on schedule/dispatch; tier1 on push/PR
    assert "schedule" in jobs["slow"]["if"]
    assert "pull_request" in jobs["tier1"]["if"]
