"""The fused GMoM round kernel's device time over the device's busy time in
the traced window.  Nothing to read where the kernel did not run."""

from bench.kernels import is_round_kernel


def read(r):
    t_kernel = r.trace.op_seconds(is_round_kernel)
    if t_kernel <= 0:
        return None
    return 100.0 * t_kernel / r.trace.busy_s()
