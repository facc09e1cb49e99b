#!/usr/bin/env python
"""Keep the paper↔code documentation honest as the registries grow.

Fails (non-zero exit / raised AssertionError from pytest) when:

* a registered aggregator, attack, or schedule is missing from
  docs/PAPER_MAP.md (every registry name must appear as `name`);
* a registry entry has an empty description (the registry IS the
  documentation surface — see aggregators.describe());
* the README aggregator table is missing a registered aggregator;
* a registered pod-sweep scenario or production mesh (repro.sim.sweep) is
  missing from the checked-in benchmarks/BENCH_pod_sweeps.json, or a
  sweep-matrix axis value (attack/schedule/aggregator/mesh) is missing
  from the docs/BENCHMARKS.md sweep tables;
* a repro.verify rule (RV1xx/RV2xx/RV3xx) is missing from the
  docs/STATIC_ANALYSIS.md catalog, or the catalog documents a rule ID
  that is no longer registered (stale docs fail too);
* a Layer-C taint surface is undocumented: a declared (or declarable)
  sanitization kind or an adversary source tag missing from the
  docs/STATIC_ANALYSIS.md tables, or a PAPER_MAP that no longer anchors
  the --taint gate to the paper's S1.3 dependency argument;
* a registered arrival schedule (repro.core.staleness) is missing from
  the docs/ASYNC.md schedule table or the PAPER_MAP synchrony rows;
* a prose doc references a repo file path that does not exist, or points
  into the build container's /root/related staging area.

Run directly::

    PYTHONPATH=src python scripts/check_docs.py

or via tier-1 (tests/test_docs_map.py).
"""

from __future__ import annotations

import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(relpath: str) -> str:
    with open(os.path.join(REPO, relpath)) as f:
        return f.read()


def collect_problems() -> list[str]:
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core import aggregators, byzantine

    problems: list[str] = []
    paper_map = _read(os.path.join("docs", "PAPER_MAP.md"))
    readme = _read("README.md")

    registries = {
        "aggregator": aggregators.describe(),
        "attack": byzantine.describe(),
        "schedule": byzantine.describe_schedules(),
    }
    for kind, rows in registries.items():
        for name, description in rows:
            if f"`{name}`" not in paper_map:
                problems.append(
                    f"{kind} {name!r} is registered but missing from "
                    "docs/PAPER_MAP.md — add its row")
            if not description.strip():
                problems.append(
                    f"{kind} {name!r} has an empty registry description")

    # The README table must match the registry row for row — names AND
    # descriptions (regenerate with aggregators.describe_markdown()).
    for row in aggregators.describe_markdown().splitlines():
        if row not in readme:
            problems.append(
                "README aggregator table drifted from the registry; "
                f"missing row: {row!r} "
                "(regenerate with repro.core.aggregators.describe_markdown())")

    problems += _pod_sweep_problems(paper_map)
    problems += _codec_problems(paper_map)
    problems += _verify_rules_problems(paper_map)
    problems += _taint_doc_problems(paper_map)
    problems += _arrival_problems(paper_map)
    problems += _dead_path_problems()
    return problems


def _arrival_problems(paper_map: str) -> list[str]:
    """The asynchrony contract: every registered arrival schedule must be
    documented where its semantics live — the docs/ASYNC.md schedule table
    AND the PAPER_MAP synchrony rows — with a non-empty registry
    description (the registry IS the documentation surface, same
    discipline as the aggregator / attack / codec registries)."""
    from repro.core import staleness

    problems: list[str] = []
    async_md = _read(os.path.join("docs", "ASYNC.md"))
    for name, description in staleness.describe():
        if f"`{name}`" not in async_md:
            problems.append(
                f"arrival schedule {name!r} is registered but missing from "
                "docs/ASYNC.md — add its row to the schedule table")
        if f"`{name}`" not in paper_map:
            problems.append(
                f"arrival schedule {name!r} is registered but missing from "
                "docs/PAPER_MAP.md — add it to the §2 synchrony-assumption "
                "rows")
        if not description.strip():
            problems.append(
                f"arrival schedule {name!r} has an empty registry "
                "description")
    return problems


# Backtick-quoted repo paths in the prose docs (`a/b.py`, `docs/X.md`, …).
# Requires a `/` so module dotted-paths don't match; skips glob/template
# candidates (`*`, `<`, `{`) and the documented-as-uncommitted scratch
# outputs under benchmarks/results/.
_DOC_PATH_RE = re.compile(
    r"`([A-Za-z0-9_.\-/]+/[A-Za-z0-9_.\-/]+"
    r"\.(?:py|md|json|yml|yaml|sh))`")
_DEAD_PATH_DOCS = ("README.md", "ROADMAP.md", "docs/ASYNC.md",
                   "docs/BENCHMARKS.md", "docs/PAPER_MAP.md",
                   "docs/STATIC_ANALYSIS.md", "docs/DESIGN.md")


def _dead_path_problems(doc_texts: dict[str, str] | None = None) -> list[str]:
    """No dead pointers in the prose docs: every backtick-quoted file path
    must exist in the repo (tried verbatim, under src/, and under
    src/repro/ — the docs use all three conventions), and no doc may
    reference the build container's /root/related staging area, which does
    not exist for readers of the published repo (the ROADMAP once pointed
    there — PR 9 replaced those with upstream URLs).

    ``doc_texts`` overrides the on-disk docs for the negative-path test in
    tests/test_docs_map.py."""
    problems: list[str] = []
    if doc_texts is None:
        doc_texts = {rel: _read(rel) for rel in _DEAD_PATH_DOCS
                     if os.path.exists(os.path.join(REPO, rel))}
    for rel, text in doc_texts.items():
        for path in sorted(set(_DOC_PATH_RE.findall(text))):
            if path.startswith("benchmarks/results/"):
                continue
            candidates = (path, os.path.join("src", path),
                          os.path.join("src", "repro", path))
            if not any(os.path.exists(os.path.join(REPO, c))
                       for c in candidates):
                problems.append(
                    f"{rel} references `{path}` but no such file exists "
                    "(tried verbatim, src/, src/repro/) — fix or drop the "
                    "dead pointer")
        for i, line in enumerate(text.splitlines(), start=1):
            if "/root/related" in line:
                problems.append(
                    f"{rel}:{i} references the /root/related staging area, "
                    "which does not exist for repo readers — cite the "
                    "upstream URL instead")
    return problems


def _codec_problems(paper_map: str) -> list[str]:
    """The wire-codec contract: every registered compression codec must be
    documented where the §1.4 cost claims live — the PAPER_MAP comm-cost
    rows AND the docs/BENCHMARKS.md wire-traffic section — with a
    non-empty registry description (same discipline as the aggregator /
    attack registries: the registry IS the documentation surface)."""
    from repro.core import compression

    problems: list[str] = []
    benchmarks_md = _read(os.path.join("docs", "BENCHMARKS.md"))
    for name, description in compression.describe():
        if f"`{name}`" not in paper_map:
            problems.append(
                f"compression codec {name!r} is registered but missing "
                "from docs/PAPER_MAP.md — add it to the §1.4 "
                "communication-cost rows")
        if f"`{name}`" not in benchmarks_md:
            problems.append(
                f"compression codec {name!r} is registered but missing "
                "from the docs/BENCHMARKS.md wire-traffic section")
        if not description.strip():
            problems.append(
                f"compression codec {name!r} has an empty registry "
                "description")
    return problems


def _verify_rules_problems(paper_map: str) -> list[str]:
    """The invariant-checker contract: rule registry ⟺ the
    docs/STATIC_ANALYSIS.md catalog, both directions."""
    import re

    from repro.verify.rules import RULES

    problems: list[str] = []
    doc = _read(os.path.join("docs", "STATIC_ANALYSIS.md"))

    for rid in RULES:
        if f"`{rid}`" not in doc:
            problems.append(
                f"verify rule {rid!r} is registered but undocumented in "
                "docs/STATIC_ANALYSIS.md — add its catalog row")
    for rid in set(re.findall(r"`(RV\d{3})`", doc)):
        if rid not in RULES:
            problems.append(
                f"docs/STATIC_ANALYSIS.md documents {rid!r} but no such "
                "rule is registered in repro.verify.rules — remove the "
                "stale row or restore the rule")
    if "repro.verify" not in paper_map:
        problems.append(
            "docs/PAPER_MAP.md does not anchor `repro.verify` "
            "(§Thm 3 collective-shape rows)")
    return problems


def _taint_doc_problems(paper_map: str) -> list[str]:
    """The Layer-C contract: every sanitization kind the influence engine
    can discover (= every value ``register(sanitization_point=...)``
    accepts) and every adversary source tag must be documented in the
    docs/STATIC_ANALYSIS.md tables, every *declared* point must be one of
    them, and PAPER_MAP must anchor the taint gate to the paper's S1.3
    arbitrary-dependency argument."""
    from repro.core import aggregators
    from repro.verify.influence import SANITIZER_KINDS

    problems: list[str] = []
    doc = _read(os.path.join("docs", "STATIC_ANALYSIS.md"))

    for kind in SANITIZER_KINDS:
        if f"`{kind}`" not in doc:
            problems.append(
                f"sanitization kind {kind!r} is recognized by the Layer-C "
                "influence engine but missing from the "
                "docs/STATIC_ANALYSIS.md bounded-op table")
    for source in ("report", "age", "attack_state"):
        if f"`{source}`" not in doc:
            problems.append(
                f"adversary source tag {source!r} is missing from the "
                "docs/STATIC_ANALYSIS.md taint-sources table")
    for name in aggregators.available():
        point = aggregators.get_aggregator(name).sanitization_point
        if point is not None and point not in SANITIZER_KINDS:
            problems.append(
                f"aggregator {name!r} declares sanitization_point "
                f"{point!r}, which the influence engine cannot discover "
                "(not in SANITIZER_KINDS)")
    if "--taint" not in paper_map:
        problems.append(
            "docs/PAPER_MAP.md does not anchor the Layer-C taint gate "
            "(`python -m repro.verify --strict --taint`) to the S1.3 "
            "arbitrary-dependency rows")
    return problems


def _pod_sweep_problems(paper_map: str) -> list[str]:
    """The pod-sweep contract: registry ⊆ checked-in record ∧ docs tables."""
    from repro.sim import sweep

    problems: list[str] = []
    benchmarks_md = _read(os.path.join("docs", "BENCHMARKS.md"))

    # every matrix axis value must be documented in the BENCHMARKS.md sweep
    # section, and the sweep module must be anchored in the paper map.
    for kind, values in (("attack", sweep.POD_ATTACKS),
                         ("schedule", sweep.POD_SCHEDULES),
                         ("aggregator", sweep.POD_AGGREGATORS),
                         ("mesh", sweep.POD_MESHES)):
        for v in values:
            if f"`{v}`" not in benchmarks_md:
                problems.append(
                    f"pod-sweep {kind} {v!r} is in the sweep matrix but "
                    "missing from the docs/BENCHMARKS.md sweep tables")
    if "repro.sim.sweep" not in paper_map:
        problems.append(
            "docs/PAPER_MAP.md does not anchor `repro.sim.sweep` "
            "(§5 communication-cost rows)")

    sweep_path = os.path.join("benchmarks", "BENCH_pod_sweeps.json")
    if not os.path.exists(os.path.join(REPO, sweep_path)):
        problems.append(
            f"{sweep_path} is not checked in "
            "(run python -m repro.sim.sweep --all)")
        return problems
    try:
        rec = json.loads(_read(sweep_path))
    except json.JSONDecodeError as e:
        problems.append(f"{sweep_path} does not parse: {e}")
        return problems
    scenarios = rec.get("scenarios", {})
    for name in sweep.available():
        if name not in scenarios:
            problems.append(
                f"pod scenario {name!r} is registered but missing from "
                f"{sweep_path} — re-record with "
                "`python -m repro.sim.sweep --all`")
    recorded_meshes = {e.get("mesh") for e in scenarios.values()}
    for mesh in sweep.POD_MESHES:
        if mesh not in recorded_meshes:
            problems.append(
                f"production mesh {mesh!r} has no recorded scenario in "
                f"{sweep_path}")

    # big-model (sharded-aggregation) cells: documented + recorded with the
    # grad_mode they claim to measure.
    if f"`{sweep.BIG_MODEL_ARCH}`" not in benchmarks_md:
        problems.append(
            f"big-model arch {sweep.BIG_MODEL_ARCH!r} is in the sweep "
            "registry but missing from the docs/BENCHMARKS.md big-model "
            "section")
    for name in sweep.BIG_MODEL_SCENARIOS:
        entry = scenarios.get(name)
        if entry is None:
            continue  # absence already reported above
        want = "gathered" if name.endswith("/gathered") else "sharded"
        if entry.get("grad_mode") != want:
            problems.append(
                f"big-model scenario {name!r} recorded "
                f"grad_mode={entry.get('grad_mode')!r}, expected {want!r} — "
                "the O(d/shards) comparison needs both modes recorded as "
                "labelled")
    return problems


def main() -> int:
    problems = collect_problems()
    for p in problems:
        print(f"check_docs: {p}")
    if problems:
        print(f"check_docs: FAILED ({len(problems)} problem(s))")
        return 1
    print("check_docs: ok — registries, PAPER_MAP, README table, "
          "the pod-sweep record/docs, the "
          "repro.verify rule catalog, the Layer-C taint tables, the "
          "ASYNC.md arrival table, and every doc-referenced file path "
          "are consistent")
    return 0


if __name__ == "__main__":
    sys.exit(main())
