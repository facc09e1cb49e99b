"""What every driver shares: the run's context, seeds, the device's memory,
host spans, the traced window and the comparison of leaf norms.

Nothing here imports the program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import statistics
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# JAX's persistent compilation cache and the traces: fixed paths inside the
# checkout, so that every run of a cell after its first finds its programs.
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")


@dataclasses.dataclass
class Context:
    """One run of one cell."""
    workload: str
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float        # perf_counter() at process start
    log: callable = print

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_start


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits: both halves count."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(ctx: Context):
    """Profile the body when ``ctx.trace``; yields a dict that receives the
    path of the ``.xplane.pb`` once the profiler has stopped.  The body is
    wrapped in the ``bench.window`` span, which bounds the traced window."""
    import jax
    out = {"path": None}
    if not ctx.trace:
        yield out
        return
    d = os.path.join(TRACE_DIR, ctx.workload)
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(d)
    try:
        with span("bench.window"):
            yield out
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True))
    out["path"] = found[-1] if found else None


def memory_peak_bytes():
    """The largest ``peak_bytes_in_use`` over the local devices, or None
    where the backend keeps no statistics."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compiled_bytes(compiled) -> int | None:
    """Device bytes one compiled program needs on its fullest device:
    arguments + temporaries + outputs - what the outputs alias."""
    mem = compiled.memory_analysis()
    if mem is None:
        return None
    return int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def leaf_norm_gap(program: list, reference: list, include=None):
    """Worst leaf's gap between two lists of per-leaf norms:
    |program - reference| over the larger of the reference's norm of that
    leaf and of the median leaf.  ``include`` (bools) leaves some out.
    Returns ``(gap, index of the worst leaf)``."""
    med = statistics.median(reference)
    worst, at = 0.0, -1
    for i, (p, r) in enumerate(zip(program, reference)):
        if include is not None and not include[i]:
            continue
        gap = abs(p - r) / max(r, med)
        if gap > worst or at < 0:
            worst, at = gap, i
    return worst, at


def nonzero_leaves(reference: list, share: float = 1e-3) -> list:
    """Leaves whose reference gradient is not nought to rounding: above
    ``share`` of the median leaf's norm."""
    med = statistics.median(reference)
    return [r > share * med for r in reference]
