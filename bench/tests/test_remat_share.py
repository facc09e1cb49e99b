"""``step.remat.busy_share``: the share of the device's busy time in ops whose
path runs through ``rematted_computation``, on the hand-made trace of
``test_scopes`` and on the recorded v5e trace."""

import gzip
import os
import re

import pytest

from bench import harness, scopes
from bench import trace as trace_lib
from bench.tests.test_scopes import FIXTURES, reader, reading, scoped  # noqa: F401

NAME = "step.remat.busy_share"


def test_remat_share_reads_the_recomputed_ops(scoped, monkeypatch):
    """Counts the ops whose path runs through ``rematted_computation``, and
    reads 0 where none does."""
    tr, prog = scoped
    r = reading(tr, counters={"steps": 2})
    monkeypatch.setattr(scopes, "of_reading", lambda r: prog)
    assert reader(NAME).read(r) == 0.0
    path = prog.scopes["fusion.12"].rsplit("/", 1)
    rematted = scopes.Program(dict(prog.scopes, **{
        "fusion.12": f"{path[0]}/checkpoint/rematted_computation/{path[1]}"}),
        prog.loops, prog.shared)
    monkeypatch.setattr(scopes, "of_reading", lambda r: rematted)
    # fusion.12 runs 140 ns in each step's group scan
    assert reader(NAME).read(r) == pytest.approx(100 * 280 / 1090)


def test_remat_share_finds_nothing_without_its_program(scoped, tmp_path,
                                                       monkeypatch):
    tr, prog = scoped
    # no trace file for the cell
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert reader(NAME).read(reading(tr)) is None
    # a program that names no scopes, as one before them did
    unnamed = {n: re.sub(r"/(group_fwd_bwd|aggregate|weiszfeld|optimizer|"
                         r"trim)(?=/)", "", p) for n, p in prog.scopes.items()}
    assert scopes.program([scopes.Module(set(unnamed), unnamed,
                                         prog.loops)]) is None
    monkeypatch.setattr(scopes, "of_reading", lambda r: None)
    assert reader(NAME).read(reading(tr)) is None


def test_remat_share_on_a_v5e_trace(tmp_path, monkeypatch):
    """A traced run of ``mistral7b.gmom.signflip`` on a TPU v5 lite (3 steps,
    seed 3400000011) under full remat: the gate/up, o and q projections and
    the attention core of its forward run again in the backward, 73 ms a
    step."""
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
    assert reader(NAME).read(r) == pytest.approx(7.825454694579547,
                                                 rel=1e-12)
