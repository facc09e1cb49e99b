"""How the benchmark finds the program's kernels in a device trace, by the
names the trace gives them."""


def is_round_kernel(op_name: str) -> bool:
    """The fused GMoM round kernel (``kernels/geomed/round.py``): a Pallas
    call, whose HLO instruction, and so its op in the trace, is named after
    the jitted ``round_aggregate_kernel`` that wraps it
    (``%round_aggregate_kernel.7`` in a compile for a v5e)."""
    return op_name.lstrip("%").split(".")[0] == "round_aggregate_kernel"
