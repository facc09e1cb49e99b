"""The fused GMoM round kernel's share of its roofline: the least time its
necessary work takes (one f32 read of the (m, d) reports and one write of
the (d,) aggregate, and the batch means' 2 m d operations;
``bench/arith.py``), times the rounds in the traced window, over the
kernel's device time in the trace.  At m=50, d=100 the bytes bound it.
Nothing to read where the kernel did not run."""

from bench import arith
from bench.kernels import is_round_kernel


def read(r):
    c = r.counters
    t_kernel = r.trace.op_seconds(is_round_kernel)
    if t_kernel <= 0 or not c.get("rounds"):
        return None
    peak = arith.peaks(r.device["kind"])
    t_min, _ = arith.roofline_seconds(c["round_kernel_flops"],
                                      c["round_bytes"], peak)
    return 100.0 * c["rounds"] * t_min / t_kernel
