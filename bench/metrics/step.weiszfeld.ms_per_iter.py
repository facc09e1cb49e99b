"""Milliseconds of one Weiszfeld iteration: the device time under the
program's ``weiszfeld`` scope in the traced window (the loop and its
starting mean) over the iterations its loop ran there.  Nothing to read
where the program names no scopes or the loop did not run."""

from bench import scopes


def read(r):
    prog = scopes.of_reading(r)
    iterations = scopes.loop_iterations(r.trace, prog, "weiszfeld")
    if not iterations:
        return None
    return 1000.0 * scopes.scope_seconds(r.trace, prog, "weiszfeld") \
        / iterations
