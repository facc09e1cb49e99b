"""The trace reduction, on a hand-made two-chip trace."""

import json
import os

import pytest

from bench import trace as trace_lib
from bench.kernels import is_round_kernel

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.fixture(scope="module")
def two_chips():
    with open(os.path.join(FIXTURES, "two_chip_trace.json")) as f:
        raw = json.load(f)
    return trace_lib.Trace(
        ops={int(c): [tuple(e) for e in evs] for c, evs in raw["ops"].items()},
        host=[tuple(e) for e in raw["host"]])


def test_busy_and_idle(two_chips):
    t = two_chips
    assert t.window() == (50, 750)
    assert t.busy(0) == [(100, 400), (500, 600), (650, 700)]
    assert t.busy(1) == [(120, 310), (320, 380), (500, 620)]
    assert t.busy_s() == pytest.approx((450 + 370) / 2 * 1e-9)
    assert t.idle_share() == pytest.approx(1 - 410 / 700)


def test_named_op_time(two_chips):
    assert two_chips.op_seconds(is_round_kernel) == pytest.approx(25e-9)
    assert is_round_kernel("%round_aggregate_kernel.12")
    assert not is_round_kernel("fusion.3")
    assert two_chips.op_seconds(lambda n: n.startswith("fusion")) == \
        pytest.approx((300 + 310) / 2 * 1e-9)


def test_collective_time_and_its_exposed_part(two_chips):
    t = two_chips
    assert t.op_seconds(trace_lib.is_collective) == pytest.approx(105e-9)
    assert t.exposed_seconds(trace_lib.is_collective) == pytest.approx(80e-9)
    assert t.op_count(trace_lib.is_collective) == 1.0
    assert trace_lib.is_collective("all-reduce-start.3")
    assert trace_lib.is_collective("%reduce-scatter.1")
    assert not trace_lib.is_collective("fusion.12")


def test_idle_gaps_by_host_span(two_chips):
    assert two_chips.gaps(0) == [("bench.dispatch", 50, 100),
                                 ("bench.make_batch", 400, 500),
                                 ("bench.wait", 600, 650),
                                 ("bench.wait", 700, 750)]
    b = two_chips.breakdown()
    assert b["device_ops"] == [["fusion.1", 200e-9], ["all-reduce.2", 150e-9],
                               ["fusion.3", 100e-9],
                               ["round_aggregate_kernel.7", 50e-9]]
    assert b["idle_gaps"][0] == ["bench.make_batch", 100e-9]
    assert [g[0] for g in b["idle_gaps"][1:]] == [
        "bench.dispatch", "bench.wait", "bench.wait"]


def test_interval_arithmetic():
    assert trace_lib.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4),
                                                                (5, 8)]
    assert trace_lib.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2),
                                                               (3, 5)]
    assert trace_lib.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
