"""Reduction of a profiler trace to device busy time, op times, collective
exposure and the host's spans in the device's idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``, into a plain ``Trace``: per chip the device
ops as (name, start, end) in nanoseconds, and the host's ``bench.*`` spans
on the same clock.  Everything after that is interval arithmetic on those
lists, so it can be checked on a small recorded trace.

The traced window is the host span ``bench.window``.  A chip's busy time
is the union of its op intervals inside the window; its idle share is one
minus busy time over the window.
"""

from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
# XLA's own opcode names for the exchanges between chips
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(name: str) -> bool:
    base = name.lstrip("%").split(".")[0]
    return any(base.startswith(c) for c in COLLECTIVES)


@dataclasses.dataclass
class Trace:
    """``ops[chip]``: the device ops (name, start_ns, end_ns);
    ``host``: the host spans (name, start_ns, end_ns)."""
    ops: dict
    host: list

    def window(self):
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
        return min(s for s, _ in spans), max(e for _, e in spans)

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def busy(self, chip):
        lo, hi = self.window()
        return clip(union((s, e) for _, s, e in self.ops[chip]), lo, hi)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return sum(length(self.busy(c)) for c in self.ops) \
            / len(self.ops) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def op_seconds(self, match) -> float:
        """Device seconds of the ops whose name ``match`` accepts, inside
        the window, averaged over the chips (their union on each chip)."""
        lo, hi = self.window()
        total = 0.0
        for chip, ops in self.ops.items():
            total += length(clip(union((s, e) for n, s, e in ops
                                       if match(n)), lo, hi))
        return total / len(self.ops) / 1e9

    def op_count(self, match) -> float:
        """Ops whose name ``match`` accepts that start inside the window,
        averaged over the chips."""
        lo, hi = self.window()
        n = sum(1 for ops in self.ops.values() for name, s, _ in ops
                if match(name) and lo <= s < hi)
        return n / len(self.ops)

    def exposed_seconds(self, match) -> float:
        """Seconds in which an op that ``match`` accepts runs and no other
        op runs on that chip, averaged over the chips."""
        lo, hi = self.window()
        total = 0.0
        for ops in self.ops.values():
            these = clip(union((s, e) for n, s, e in ops if match(n)), lo, hi)
            others = union((s, e) for n, s, e in ops if not match(n))
            total += length(subtract(these, others))
        return total / len(self.ops) / 1e9

    def gaps(self, chip):
        """The idle gaps of one chip inside the window, each labelled with
        the host span that covers most of it ("none" where no span does)."""
        lo, hi = self.window()
        idle = subtract([(lo, hi)], self.busy(chip))
        spans = [(n, s, e) for n, s, e in self.host if n != WINDOW_SPAN]
        out = []
        for s, e in idle:
            best, cover = "none", 0
            for n, hs, he in spans:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = n, c
            out.append((best, s, e))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time on chip 0, and its longest
        idle gaps by the host span they fell in."""
        chip = min(self.ops)
        lo, hi = self.window()
        per = {}
        for n, s, e in self.ops[chip]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per[n] = per.get(n, 0.0) + d
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(chip), key=lambda g: g[1] - g[2])[:top]
        return {"device_ops": [[n, t / 1e9] for n, t in ops],
                "idle_gaps": [[n, (e - s) / 1e9] for n, s, e in gaps]}


def from_profile(pd, chips: int | None = None) -> Trace:
    """A ``Trace`` from ``jax.profiler.ProfileData``: the ``XLA Ops`` line
    of each TPU device plane, and every host event named ``bench.*``."""
    ops, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chips is not None and chip >= chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(chip, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
            ops.setdefault(chip, [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    if not ops:
        raise ValueError("the trace has no TPU device plane")
    return Trace(ops=ops, host=host)


def load(path: str, chips: int | None = None) -> Trace:
    from jax.profiler import ProfileData
    if path is None:
        raise ValueError("the run wrote no trace")
    return from_profile(ProfileData.from_file(path), chips=chips)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader is given: the reduced trace, the
    driver's counters for the traced window, the device, and the cell's
    entry, configuration and traffic."""
    trace: Trace
    counters: dict
    device: dict
    cell: dict
    config: dict
    traffic: dict
