"""Device time per superstep outside Pallas calls, in ms: the busy time of
the traced window less its Pallas kernels' time, over the supersteps run
in it.  Layer: the XLA ops of a head/tail path: the tail's step between
the superstep's two fused launches (``core/dglmnet.py``), the whole-tail
gradients of screening and the KKT checks, and the working-set gathers
(``data/design.py``)."""


def read(ctx):
    t, steps = ctx.trace, ctx.counters.get("supersteps", 0)
    if t is None or not steps or not t.busy_by_device:
        return None
    return 1e3 * (t.busy_s - t.kernel_s / len(t.busy_by_device)) / steps
