"""Model FLOP utilisation of the training step: the forward and backward
operations per token (``bench/arith.py``, without remat's recompute) times
the tokens of the steps in the traced window, over the window's length,
the chips and their bf16 peak (``bench/peaks.json``)."""

from bench import arith


def read(r):
    c = r.counters
    if not c.get("tokens"):
        return None
    peak = arith.peaks(r.device["kind"])["bf16_flops_per_s"]
    rate = c["flops_per_token"] * c["tokens"] / r.trace.window_s()
    return 100.0 * rate / (r.device["count"] * peak)
