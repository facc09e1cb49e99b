"""Utilisation of the whole GD round: the operations every round needs (the
workers' residuals and gradients, 4 N d; ``bench/arith.py``) times the
rounds in the traced window, over the window's length, the chips and their
bf16 peak (``bench/peaks.json``)."""

from bench import arith


def read(r):
    c = r.counters
    if not c.get("rounds"):
        return None
    peak = arith.peaks(r.device["kind"])["bf16_flops_per_s"]
    rate = c["round_flops"] * c["rounds"] / r.trace.window_s()
    return 100.0 * rate / (r.device["count"] * peak)
