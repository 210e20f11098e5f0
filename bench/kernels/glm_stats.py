"""``kernels/glm_stats.py``: per-row link statistics (loss, -dl/dm, weight)
of a GLM family at the margins.

Operands: (y, xb, mask) as (R, 128) blocks; three (R, 128) outputs."""

# elementwise operations per row of the logistic statistics: y*m, exp, the
# log1p, the sigmoid, s = y*sig, w = sig*(1-sig), and three mask products
ROW_FLOPS = 10.0


def cost(operands, ctx):
    R, C = operands[0][1]
    n = R * C
    return ROW_FLOPS * n, 4.0 * 6 * n
