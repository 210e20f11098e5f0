"""All Pallas kernels' share of their roofline, in %: the sum over every
kernel call in the traced window of its roofline time (the larger of its
operations over peak FLOP/s and its bytes over peak HBM bandwidth, from the
formula in ``kernels/<module>.py``) over the sum of the calls' device
time.  Layer: the kernels (``kernels/*.py``)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.kernel_s <= 0:
        return None
    return 100.0 * t.roofline_s / t.kernel_s
