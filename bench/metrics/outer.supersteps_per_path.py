"""Supersteps per λ-path: the session's superstep counter over the traced
paths, divided by their number.  Layer: the outer loop
(``core/solver.py`` ``_run`` and ``_path_impl``)."""


def read(ctx):
    paths = ctx.counters.get("paths", 0)
    return ctx.counters["supersteps"] / paths if paths else None
