"""KKT rounds per λ point: the outer-loop runs (``_run`` calls) the λ-path
loop made over the λ points it finished, in the traced paths.  1.0
means the strong rule never needed a re-fit; its inverse is the share of
rounds that were useful.  Layer: the λ-path loop (``core/solver.py``
``_path_impl``)."""


def read(ctx):
    lambdas = ctx.counters.get("lambdas", 0)
    return ctx.counters["kkt_rounds"] / lambdas if lambdas else None
