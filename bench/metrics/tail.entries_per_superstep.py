"""Tail entries read per superstep: the session's ``tail_entries`` counter
(the sparse-tail entries a head/tail design's superstep reads, for its
stats and for its margin delta) over the supersteps of the traced paths.
Layer: the design operators (``data/design.py`` ``HeadTailDesign``).  None
for a program that does not count them."""


def read(ctx):
    steps = ctx.counters.get("supersteps", 0)
    if "tail_entries" not in ctx.counters or not steps:
        return None
    return ctx.counters["tail_entries"] / steps
