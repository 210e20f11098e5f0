"""Device idle time per superstep spent in the outer loop's turnaround, in
ms: the idle gaps of the fullest device whose middle falls in the
program's ``solver/superstep`` (dispatch), ``solver/sync`` (the wait for
the superstep's metrics) or ``solver/run`` (the host's bookkeeping between
supersteps) spans, over the supersteps run in the traced window.  Layer:
the outer loop (``core/solver.py`` ``GLMSolver._run``).  None for a program
that does not count its KKT rounds: it has no such span tree."""

SPANS = ("solver/superstep", "solver/sync", "solver/run")


def read(ctx):
    t, steps = ctx.trace, ctx.counters.get("supersteps", 0)
    if t is None or not steps or not t.busy_by_device or \
            "kkt_rounds" not in ctx.counters:
        return None
    return 1e3 * sum(t.idle_by_span.get(s, 0.0) for s in SPANS) / steps
