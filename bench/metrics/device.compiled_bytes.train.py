"""Device bytes the compiled training step needs on its fullest device,
from ``compiled.memory_analysis()``: arguments, temporaries and outputs,
less what the outputs alias (the donated parameters and moments)."""


def read(r):
    return r.counters.get("compiled_bytes")
