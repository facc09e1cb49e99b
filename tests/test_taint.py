"""Layer C taint analysis: the influence-lattice engine unit tests, the
per-aggregator certificate table (the PR-5 soundness split rediscovered
from dataflow), precision fixtures (tainted reads inside bounded ops must
NOT fire), the deliberately-leaky dummy rejection in both shard modes
(subprocess: forced 8-device host mesh), the multi-round trace, the SARIF
CLI surface, and the ignore audit."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.verify import influence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TAINT_FIXTURES = os.path.join(REPO, "tests", "fixtures", "verify", "taint")

RAW_REPORT = influence.raw("report")
CLEAN = influence.CLEAN_LABEL


def labels_of(fn, in_labels, *example_args):
    jaxpr = jax.make_jaxpr(fn)(*example_args)
    return influence.run_jaxpr(jaxpr, in_labels)


# --------------------------------------------------------------------------
# influence engine: per-primitive transfer rules

def test_sort_demotes_to_order_stat():
    x = jnp.zeros((8,))
    (out,) = labels_of(lambda g: jnp.median(g), [RAW_REPORT], x)
    assert out.level == influence.BOUNDED
    assert "order_stat" in out.kinds and out.sources == {"report"}


def test_mul_by_mask_does_not_launder():
    """The norm_select unsoundness: masking a raw report by a 0/1 mask
    (even one derived through an order statistic) rescales it — RAW."""
    x = jnp.zeros((8, 4))

    def f(g):
        norms = jnp.sqrt(jnp.sum(jnp.square(g), axis=1))
        keep = norms <= jnp.median(norms)
        return jnp.sum(g * keep[:, None], axis=0) / jnp.sum(keep)

    (out,) = labels_of(f, [RAW_REPORT], x)
    assert out.level == influence.RAW


def test_sum_and_mean_stay_raw():
    x = jnp.zeros((8, 4))
    (out,) = labels_of(lambda g: jnp.mean(g, axis=0), [RAW_REPORT], x)
    assert out.level == influence.RAW and out.kinds == frozenset()


def test_reduce_max_scale_stays_raw():
    """An int8-codec amax scale derived from the report is RAW — the
    dequantize-by-tainted-scale bug class."""
    x = jnp.zeros((8, 4))
    (out,) = labels_of(
        lambda g: jnp.max(jnp.abs(g)) * jnp.ones((4,)), [RAW_REPORT], x)
    assert out.level == influence.RAW


def test_gather_with_tainted_index_is_rank_select():
    x = jnp.zeros((8, 4))

    def f(g):
        norms = jnp.sum(jnp.square(g), axis=1)
        return g[jnp.argmin(norms)]

    (out,) = labels_of(f, [RAW_REPORT], x)
    assert out.level == influence.BOUNDED
    assert "rank_select" in out.kinds


def test_gather_with_clean_index_passes_label_through():
    x = jnp.zeros((8, 4))
    (out,) = labels_of(lambda g: g[0], [RAW_REPORT], x)
    assert out.level == influence.RAW


def test_select_n_over_clean_constants_is_sign_vote():
    x = jnp.zeros((8, 4))

    def f(g):
        votes = jnp.sum(jnp.sign(g).astype(jnp.float32), axis=0)
        return jnp.where(votes >= 0, 1.0, -1.0)

    (out,) = labels_of(f, [RAW_REPORT], x)
    assert out.level == influence.BOUNDED
    assert "sign_vote" in out.kinds


def test_select_n_with_tainted_branch_joins():
    x = jnp.zeros((8, 4))

    def f(g):
        s = jnp.sum(g, axis=0)
        return jnp.where(s >= 0, s, -1.0)

    (out,) = labels_of(f, [RAW_REPORT], x)
    assert out.level == influence.RAW


def test_clamp_against_clean_bounds_demotes_to_clip():
    x = jnp.zeros((8, 4))
    (out,) = labels_of(
        lambda g: jax.lax.clamp(-1.0, jnp.sum(g, axis=0), 1.0),
        [RAW_REPORT], x)
    assert out.level == influence.BOUNDED and "clip" in out.kinds


def test_bool_outputs_cap_and_chains_stay_bounded():
    x = jnp.zeros((8,))

    def f(g):
        a = g > 0.0
        b = g < 1.0
        return jnp.sum(jnp.logical_and(a, b).astype(jnp.int32))

    (out,) = labels_of(f, [RAW_REPORT], x)
    assert out.level == influence.BOUNDED
    assert out.kinds == frozenset()   # a bool is not a sanitizer


def test_while_fixpoint_terminates_and_carries_taint():
    x = jnp.zeros((4,))

    def f(g):
        def body(c):
            i, acc = c
            return i + 1, acc + g
        return jax.lax.while_loop(lambda c: c[0] < 10, body,
                                  (0, jnp.zeros((4,))))[1]

    (out,) = labels_of(f, [RAW_REPORT], x)
    assert out.level == influence.RAW


def test_arity_mismatch_raises():
    jaxpr = jax.make_jaxpr(lambda a, b: a + b)(1.0, 2.0)
    with pytest.raises(ValueError, match="arity"):
        influence.run_jaxpr(jaxpr, [CLEAN])


# --------------------------------------------------------------------------
# the certificate table: PR-5 soundness split from dataflow alone

EXPECTED = {
    # ROBUST family — BOUNDED with the declared sanitizer on the dataflow
    "coord_median": (influence.BOUNDED, {"order_stat"}),
    "coord_trimmed_mean": (influence.BOUNDED, {"order_stat"}),
    "coordinate_median": (influence.BOUNDED, {"order_stat"}),
    "trimmed_mean": (influence.BOUNDED, {"order_stat"}),
    "geomed": (influence.BOUNDED, {"weiszfeld"}),
    "gmom_per_leaf": (influence.BOUNDED, {"weiszfeld"}),
    "gmom": (influence.BOUNDED, {"order_stat", "weiszfeld"}),
    "int8_gmom": (influence.BOUNDED, {"order_stat", "weiszfeld"}),
    "norm_filter_gmom": (influence.BOUNDED, {"order_stat", "weiszfeld"}),
    "krum": (influence.BOUNDED, {"order_stat", "rank_select"}),
    "sign_sgd_majority": (influence.BOUNDED, {"sign_vote"}),
    # KNOWN-UNSOUND family — RAW no matter what robust ops appear upstream
    "mean": (influence.RAW, set()),
    "random_select": (influence.RAW, set()),
    "norm_select": (influence.RAW, {"order_stat"}),
    "norm_clip_mean": (influence.RAW, {"order_stat"}),
}

KNOWN_UNSOUND = {"mean", "norm_select", "norm_clip_mean"}


def test_certificate_table_unsharded():
    from repro.core import aggregators
    from repro.verify import taint
    names = [n for n in aggregators.available() if not n.startswith("_")]
    assert set(names) == set(EXPECTED), "table drifted from the registry"
    for name in names:
        rep = taint.classify_aggregator(name)
        level, kinds = EXPECTED[name]
        assert (rep.level, set(rep.kinds)) == (level, kinds), \
            (name, rep.level, sorted(rep.kinds))


def test_soundness_split_rediscovered_from_dataflow():
    """The acceptance-criteria core: ROBUST ⊆ bounded and the PR-5
    KNOWN-UNSOUND set ⊆ unbounded, with zero name-based special cases in
    the engine — and every bounded rule's declaration matches a
    discovered kind."""
    from repro.core import aggregators
    from repro.verify import taint
    for name in (n for n in aggregators.available()
                 if not n.startswith("_")):
        rep = taint.classify_aggregator(name)
        declared = aggregators.get_aggregator(name).sanitization_point
        if name in KNOWN_UNSOUND:
            assert not rep.bounded, name
            assert declared is None, name
        if declared is not None:
            assert rep.bounded and declared in rep.kinds, \
                (name, declared, sorted(rep.kinds))


def test_certificates_clean_of_findings():
    from repro.core import aggregators
    from repro.verify import taint
    for name in (n for n in aggregators.available()
                 if not n.startswith("_")):
        assert taint.check_aggregator_taint(name) == [], name


# --------------------------------------------------------------------------
# fixture corpus: the leaky dummy fires, the precision dummy does not

def _load_fixture(modname):
    import importlib
    if TAINT_FIXTURES not in sys.path:
        sys.path.insert(0, TAINT_FIXTURES)
    return importlib.import_module(modname)


def test_leaky_dummy_rejected_rv301_unsharded():
    from repro.verify import contracts, taint
    mod = _load_fixture("leaky_scale")
    try:
        fs = taint.check_aggregator_taint(mod.NAME)
        assert fs and all(f.rule == "RV301" for f in fs), \
            [f.format() for f in fs]
        assert any("sanitization_point='weiszfeld'" in f.message
                   for f in fs)
        assert all(f.path == f"<aggregator:{mod.NAME}>" for f in fs)
    finally:
        mod.unregister()
        contracts.clear_trace_cache()


def test_gram_form_weiszfeld_demotes_at_the_combine():
    """The Gram-form geometric median carries coefficients out of its loop
    (marked ``coef``); their combine with the raw reports is what certifies
    BOUNDED with the ``weiszfeld`` kind."""
    from repro.core import geometric_median_pytree
    x = jnp.zeros((5, 6))
    (out,) = labels_of(lambda g: geometric_median_pytree([g])[0],
                       [RAW_REPORT], x)
    assert out.level == influence.BOUNDED and not out.coef
    assert out.kinds == {"weiszfeld"} and out.sources == {"report"}


def _inverse_norm_coefficients(g):
    inv = 1.0 / jnp.sqrt(jnp.sum(jnp.square(g), axis=1) + 1e-24)
    return inv / jnp.sum(inv)


def _coefficients_from_another_loop(g):
    # a loop over the same (k,) coefficients that never takes a distance
    # to its carried point: no sqrt -> 1/d chain, so the detector is silent
    norms = jnp.sum(jnp.square(g), axis=1)

    def body(carry):
        i, c = carry
        w = c * norms
        return i + 1, w / jnp.sum(w)

    return jax.lax.while_loop(lambda carry: carry[0] < 4, body,
                              (0, jnp.full((g.shape[0],), 0.2)))[1]


@pytest.mark.parametrize("coefficients", [_inverse_norm_coefficients,
                                          _coefficients_from_another_loop])
def test_combine_without_a_weiszfeld_loop_stays_raw(coefficients):
    """The same combine, with coefficients that no firing Weiszfeld loop
    carried out, launders nothing."""
    from repro.core.geometric_median import weighted_sum
    x = jnp.zeros((5, 6))
    (out,) = labels_of(lambda g: weighted_sum(coefficients(g), g),
                       [RAW_REPORT], x)
    assert out.level == influence.RAW and "weiszfeld" not in out.kinds


def test_inverse_distance_carry_beside_a_raw_sum_stays_raw():
    """A loop that carries normalized 1/d weights next to a raw running sum,
    with no product of the weights and the reports, is no Weiszfeld loop:
    the sum stays RAW and the weights get no ``coef`` mark."""
    x = jnp.zeros((5, 6))

    def fn(g):
        def body(carry):
            i, c, acc = carry
            inv = 1.0 / jnp.sqrt(jnp.sum(jnp.square(g - acc), axis=1) + 1e-24)
            return i + 1, inv / jnp.sum(inv), acc + g[0]

        _, c, acc = jax.lax.while_loop(
            lambda carry: carry[0] < 4, body,
            (0, jnp.full((g.shape[0],), 0.2), jnp.zeros(g.shape[1:])))
        return c, acc

    c, acc = labels_of(fn, [RAW_REPORT], x)
    assert acc.level == influence.RAW and "weiszfeld" not in acc.kinds
    assert c.level == influence.RAW and not c.coef


def test_coef_combine_dummy_certifies_raw():
    """The registry form of the precision check above: a registered rule
    whose combine uses closed-form inverse-distance coefficients."""
    from repro.verify import contracts, taint
    mod = _load_fixture("coef_combine")
    try:
        rep = taint.classify_aggregator(mod.NAME)
        assert rep.level == influence.RAW, rep
        assert "weiszfeld" not in rep.kinds
    finally:
        mod.unregister()
        contracts.clear_trace_cache()


def test_clean_clip_zero_false_positives():
    """Precision: a dummy that READS tainted values everywhere (median
    norm envelope, coordinate-median base) but only inside bounded ops
    must produce zero RV301/RV303."""
    from repro.verify import contracts, taint
    mod = _load_fixture("clean_clip")
    try:
        rep = taint.classify_aggregator(mod.NAME)
        assert rep.bounded and "order_stat" in rep.kinds
        assert taint.check_aggregator_taint(mod.NAME) == []
    finally:
        mod.unregister()
        contracts.clear_trace_cache()


def test_norm_filter_gmom_precision():
    """The production analogue of the precision fixture: its norm filter
    reads every raw report, yet the certificate stays bounded."""
    from repro.verify import taint
    rep = taint.classify_aggregator("norm_filter_gmom")
    assert rep.bounded
    assert taint.check_aggregator_taint("norm_filter_gmom") == []


def test_undeclared_but_bounded_dummy_fires_rv303():
    """A rule whose dataflow IS robust but whose registration forgot the
    declaration: the certificate comparison flags the stale metadata."""
    from repro.core import aggregators
    from repro.verify import contracts, taint

    @aggregators.register("_test_undeclared_median",
                          "test-only: coordinate median with no declared "
                          "sanitization_point")
    def _undeclared(stacked, **_kw):
        return aggregators.coordinate_median_aggregator(stacked)

    try:
        fs = taint.check_aggregator_taint("_test_undeclared_median")
        assert [f.rule for f in fs] == ["RV303"], [f.format() for f in fs]
        assert "stale" in fs[0].message
    finally:
        aggregators._REGISTRY.pop("_test_undeclared_median", None)
        contracts.clear_trace_cache()


# --------------------------------------------------------------------------
# the multi-round trace

def test_round_trace_green():
    from repro.verify import taint
    assert taint.check_round_taint() == []


def test_round_trace_section_labels():
    from repro.verify import taint
    rows = taint.classify_round()
    by_section = {}
    for section, _path, label in rows:
        by_section.setdefault(section, []).append(label)
    # reports reach params only through the bounded aggregator channel
    assert all(l.level == influence.BOUNDED
               for l in by_section["params"])
    assert all(l.level < influence.RAW for l in by_section["opt_state"])
    assert all(l.level < influence.RAW for l in by_section["metrics"])
    # ages couple rounds through timing only — never report values
    for l in by_section["stale_buffer.age"]:
        assert "report" not in l.sources
    # the buffered last reports are adversary memory: necessarily RAW
    assert any(l.level == influence.RAW
               for l in by_section["stale_buffer.grads"])


def test_round_red_paths_fire(monkeypatch):
    """RV301/RV302 finding logic over fabricated round labels: a RAW
    params leaf, a RAW metrics leaf, and a report-steered age."""
    from repro.verify import taint
    rows = [
        ("params", "['w']", influence.raw("report")),
        ("metrics", "['agg_grad_norm']", influence.raw("report")),
        ("stale_buffer.age", "", influence.Label(
            level=influence.BOUNDED, kinds=frozenset({"order_stat"}),
            sources=frozenset({"report"}))),
        ("attack_state", "['ema_norm']", influence.raw("attack_state")),
    ]
    monkeypatch.setattr(taint, "classify_round", lambda **_kw: rows)
    fs = taint.check_round_taint()
    assert sorted(f.rule for f in fs) == ["RV301", "RV302", "RV302"]
    assert any("params['w']" in f.message for f in fs)
    assert any("report VALUES" in f.message for f in fs)
    assert all(f.path == taint.ROUND_ANCHOR for f in fs)


# --------------------------------------------------------------------------
# shard_map parity (subprocess: the virtual-device flag must be set
# before jax initializes)

SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, {fixtures!r})
    from repro.verify import contracts, influence, taint

    # parity: one aggregator per sanitizer family keeps its certificate
    # under the shard_map lowering (psum partials, per-shard bodies)
    for name, kind in [("gmom", "weiszfeld"), ("coord_median", "order_stat"),
                       ("krum", "rank_select"),
                       ("sign_sgd_majority", "sign_vote")]:
        rep = taint.classify_aggregator(name, mode="shard_map")
        assert rep.bounded and kind in rep.kinds, \\
            (name, rep.level, sorted(rep.kinds))
        assert taint.check_aggregator_taint(name, mode="shard_map") == []

    rep = taint.classify_aggregator("mean", mode="shard_map")
    assert rep.level == influence.RAW

    # the leaky dummy is rejected under shard_map too
    import leaky_scale
    try:
        fs = taint.check_aggregator_taint(leaky_scale.NAME,
                                          mode="shard_map")
        assert fs and all(f.rule == "RV301" for f in fs), \\
            [f.format() for f in fs]
        assert all("shard_map" in f.message for f in fs)
    finally:
        leaky_scale.unregister()
        contracts.clear_trace_cache()
    print("OK")
""").format(fixtures=TAINT_FIXTURES)


def test_shard_map_parity_and_leaky_rejection():
    res = subprocess.run(
        [sys.executable, "-c", SHARD_MAP_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-4000:])
    assert "OK" in res.stdout


# --------------------------------------------------------------------------
# nightly: the full aggregator × codec × mode matrix (RV301 on every
# cell, the declared↔discovered comparison on canonical cells only)

FULL_MATRIX_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from repro.verify import taint
    fs = taint.run_taint(full_matrix=True, log=lambda *a, **k: None)
    assert fs == [], [f.format() for f in fs]
    print("OK")
""")


@pytest.mark.slow
def test_full_matrix_clean():
    res = subprocess.run(
        [sys.executable, "-c", FULL_MATRIX_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-4000:])
    assert "OK" in res.stdout


# --------------------------------------------------------------------------
# CLI: SARIF serialization + the ignore audit

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "repro.verify", *args],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))


def fx(name):
    return os.path.join(REPO, "tests", "fixtures", "verify", name)


def test_cli_sarif_stdout_is_machine_parseable():
    res = _run_cli("--layer", "a", "--strict", "--format", "sarif",
                   "--paths", fx("rv102_bad.py"))
    assert res.returncode == 1, (res.stdout, res.stderr)
    doc = json.loads(res.stdout)       # progress went to stderr
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert {r["ruleId"] for r in results} == {"RV102"}
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] >= 1
    rules = doc["runs"][0]["tool"]["driver"]["rules"]
    assert [r["id"] for r in rules] == ["RV102"]
    assert "[verify]" in res.stderr and "[verify]" not in res.stdout


def test_cli_sarif_output_file_written_even_under_strict(tmp_path):
    out = tmp_path / "verify.sarif"
    res = _run_cli("--layer", "a", "--strict", "--format", "sarif",
                   "--output", str(out), "--paths", fx("rv102_bad.py"))
    assert res.returncode == 1
    doc = json.loads(out.read_text())
    assert doc["runs"][0]["results"]


def test_cli_audit_ignores_clean_tree():
    res = _run_cli("--audit-ignores")
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "0 stale" in res.stdout
    # the one real escape hatch in the tree is listed with its reason
    assert "launch/steps.py" in res.stdout
    assert "eval_shape only traces" in res.stdout


def test_cli_audit_ignores_fails_on_stale_rule_id():
    res = _run_cli("--audit-ignores", "--paths", fx("ignore_unknown.py"))
    assert res.returncode == 1, (res.stdout, res.stderr)
    assert "STALE" in res.stdout


def test_cli_taint_layer_only():
    res = _run_cli("--layer", "c", "--strict", "--aggregators",
                   "coord_median", "mean")
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "layer C" in res.stdout
