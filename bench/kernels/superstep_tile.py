"""``kernels/superstep_tile.py``: the two launches of the fused dense
superstep.

``stats_gram_solve`` (8 operands: sel (nt + 1,), Xt (nt, n_pad, T), y, xb,
mask (R, 128), beta, penf (nt, 1, T), params (4,)): link statistics, the
Gram and gradient of every live tile, and each live tile's T-step chain.
Dead tiles issue no design DMA and no Gram work; how many tiles are live is
a runtime scalar, taken as the session's counted tile launches per
superstep (``ctx["live_tiles"]``), else one.

``margin_ls`` (6 operands: alphas (K,), Xt, dbeta (nt, 1, T), y, xb, mask):
the margin delta X dbeta over every tile and every candidate's loss."""

STATS_FLOPS = 10.0     # per row, as kernels/glm_stats.py
# per row and candidate: the shifted margin (2), y*m, exp, log1p, the mask
# product and the running sum
CAND_FLOPS = 7.0


def stats_gram_solve(nt, n_pad, T, live):
    live = min(float(live), nt)
    gram = live * (2.0 * n_pad * T * T + 3.0 * n_pad * T)
    chain = live * (2.0 * T * T + 10.0 * T)
    flops = gram + chain + STATS_FLOPS * n_pad
    nbytes = 4.0 * (live * n_pad * T + 6 * n_pad + live * (T * T + 4 * T))
    return flops, nbytes


def margin_ls(nt, n_pad, T, K):
    flops = 2.0 * nt * n_pad * T + CAND_FLOPS * K * n_pad
    nbytes = 4.0 * (nt * n_pad * T + 4 * n_pad + nt * T + 2 * K)
    return flops, nbytes


def cost(operands, ctx):
    nt, n_pad, T = operands[1][1]
    if len(operands) == 8:
        return stats_gram_solve(nt, n_pad, T, ctx.get("live_tiles", 1.0))
    return margin_ls(nt, n_pad, T, operands[0][1][0])
