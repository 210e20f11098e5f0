"""The whole superstep's share of the chips' peak, in %: the operations the
mathematics of a superstep needs on the cell's data (counted by the data's
generator from the rows' nonzeros, independent of layout and kernels, for
as many tiles as the session counted live per superstep), times the
supersteps in the traced window, over (window seconds x chips x peak bf16
FLOP/s)."""


def read(ctx):
    t, steps = ctx.trace, ctx.counters.get("supersteps", 0)
    peak = ctx.peaks.get("flops_bf16")
    if t is None or not steps or not peak or t.window_s <= 0:
        return None
    live = ctx.counters["sweep_tile_launches"] / steps
    flops = ctx.problem.superstep_flops(ctx.config["solver"]["tile_size"],
                                        live)
    return 100.0 * flops * steps / (t.window_s * ctx.chips * peak)
