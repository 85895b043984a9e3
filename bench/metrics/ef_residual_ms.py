"""ef_residual_ms: device time of the error-feedback residual, scope
``ef.residual``: ``u = g + e`` and ``e' = u - selected``, in ms per
step, averaged over the chips: the step's class-``step`` ops split by
the layers the program names (``bench/scopes.py``).  The nine such
metrics sum to ``step_xla_ms``.  None in a run that kept no split.
"""


def read(ctx):
    split = ctx.get("scopes")
    return None if split is None else split.metrics_ms(
        ctx["steps"]).get("ef_residual_ms")
