"""Weiszfeld iterations a step: the runs of the reference geometric
median's loop body (the program's ``weiszfeld`` scope) in the traced
window over the window's steps, each of which runs the loop once.  Nothing
to read where the program names no scopes or the loop did not run (the
fused path)."""

from bench import scopes


def read(r):
    iterations = scopes.loop_iterations(r.trace, scopes.of_reading(r),
                                        "weiszfeld")
    if not iterations or not r.counters.get("steps"):
        return None
    return iterations / r.counters["steps"]
