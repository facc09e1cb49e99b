"""Tier-1 wrapper around scripts/check_docs.py: the paper↔code map
(docs/PAPER_MAP.md), the README aggregator table, the checked-in pod-sweep
record and every doc-referenced file path must stay consistent with the
live registries and the tree."""

import os
import sys

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def test_docs_registries_consistent():
    sys.path.insert(0, SCRIPTS)
    try:
        import check_docs
        problems = check_docs.collect_problems()
    finally:
        sys.path.remove(SCRIPTS)
    assert not problems, "\n".join(problems)


def test_undocumented_codec_fails_check_docs():
    """Registering a wire codec without documenting it must fail the docs
    gate, same as an undocumented aggregator or attack."""
    from repro.core import compression
    sys.path.insert(0, SCRIPTS)
    try:
        import check_docs
        compression.register(
            "_test_undocumented_codec",
            "temporary codec for the docs-gate test",
            encode=lambda tree, **kw: tree,
            decode=lambda payload, like, **kw: payload)
        problems = check_docs._codec_problems(
            check_docs._read(os.path.join("docs", "PAPER_MAP.md")))
        assert any("_test_undocumented_codec" in p and "PAPER_MAP" in p
                   for p in problems), problems
        assert any("_test_undocumented_codec" in p and "BENCHMARKS" in p
                   for p in problems), problems
    finally:
        compression._REGISTRY.pop("_test_undocumented_codec", None)
        sys.path.remove(SCRIPTS)


def test_undocumented_arrival_fails_check_docs():
    """The docs/ASYNC.md contract: registering an arrival schedule without
    adding it to the ASYNC.md table AND the PAPER_MAP synchrony rows must
    fail the docs gate (exit != 0 via collect_problems)."""
    from repro.core import staleness
    sys.path.insert(0, SCRIPTS)
    try:
        import check_docs

        @staleness.register_arrival(
            "_test_undocumented_arrival",
            "temporary arrival schedule for the docs-gate test")
        def _builder(*, num_workers, staleness_bound, **_kw):
            return staleness.make_arrival(
                "all_sync", num_workers=num_workers,
                staleness_bound=staleness_bound)

        problems = check_docs._arrival_problems(
            check_docs._read(os.path.join("docs", "PAPER_MAP.md")))
        assert any("_test_undocumented_arrival" in p and "ASYNC" in p
                   for p in problems), problems
        assert any("_test_undocumented_arrival" in p and "PAPER_MAP" in p
                   for p in problems), problems
    finally:
        staleness._ARRIVAL_REGISTRY.pop("_test_undocumented_arrival", None)
        staleness._ARRIVAL_DESCRIPTIONS.pop("_test_undocumented_arrival",
                                            None)
        sys.path.remove(SCRIPTS)


def test_dead_doc_path_fails_check_docs():
    """A prose doc referencing a nonexistent repo file — or the build
    container's /root/related staging area — must fail the docs gate."""
    sys.path.insert(0, SCRIPTS)
    try:
        import check_docs
        fake = {"docs/FAKE.md":
                "see `src/repro/core/no_such_module.py` and the exemplar "
                "under /root/related/some_repo/thing.py"}
        problems = check_docs._dead_path_problems(doc_texts=fake)
        assert any("no_such_module.py" in p for p in problems), problems
        assert any("/root/related" in p for p in problems), problems
        # and the real docs tree is clean
        assert check_docs._dead_path_problems() == []
    finally:
        sys.path.remove(SCRIPTS)
