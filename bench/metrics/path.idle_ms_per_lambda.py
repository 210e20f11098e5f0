"""Device idle time per λ point spent in the λ-path loop, in ms: the idle
gaps of the fullest device whose middle falls in the program's
``solver/path``, ``solver/lambda`` (warm-state reset, β copied to the
host), ``solver/screen`` (the strong-rule gradient and mask) or
``solver/kkt`` (the KKT gradient, its read-back and the violation test)
spans, over the λ points the traced paths finished.  Layer: the λ-path
loop (``core/solver.py`` ``_path_impl``, ``fit_path``)."""

SPANS = ("solver/path", "solver/lambda", "solver/screen", "solver/kkt")


def read(ctx):
    t, lambdas = ctx.trace, ctx.counters.get("lambdas", 0)
    if t is None or not lambdas or not t.busy_by_device:
        return None
    return 1e3 * sum(t.idle_by_span.get(s, 0.0) for s in SPANS) / lambdas
