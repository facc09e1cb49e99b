"""The grouped expert matmuls' share of their roofline: the least time
their necessary work takes (operations and bytes from the assignments to
held experts that the step counted in the traced window,
``bench/arith_moe.py``; forward and backward, without remat's second
forward) over their device time in the trace (``experts`` scope and the
grouped matmul kernels).  Nothing to read where they did not run."""

from bench import arith, arith_moe, moe_layers


def read(r):
    c = r.counters
    t = moe_layers.layer_seconds(r, ("experts",), grouped_matmuls=True)
    if not t or not c.get("moe_local_assignments"):
        return None
    flops = arith_moe.expert_matmul_flops(r.config,
                                          c["moe_local_assignments"])
    nbytes = arith_moe.expert_matmul_bytes(
        r.config, c["moe_local_assignments"], c["moe_expert_calls"])
    t_min, _ = arith.roofline_seconds(flops, nbytes,
                                      arith.peaks(r.device["kind"]))
    return 100.0 * t_min / t
