"""Device busy time per superstep, in ms: the union of op intervals in the
traced window (mean over the chips), over the supersteps run in it.
Layer: the superstep (``core/dglmnet.py``, ``core/cd.py``,
``core/linesearch.py``) and everything it launches."""


def read(ctx):
    t, steps = ctx.trace, ctx.counters.get("supersteps", 0)
    if t is None or not steps or not t.busy_by_device:
        return None
    return 1e3 * t.busy_s / steps
