"""ef_compact_ms: device time of error-feedback compaction, scope
``ef.compact``: the cumsum of the kept mask, and the search and gathers
that pack the kept values, in ms per step, averaged over the chips: the
step's class-``step`` ops split by the layers the program names
(``bench/scopes.py``).  The nine such metrics sum to ``step_xla_ms``.
None in a run that kept no split.
"""


def read(ctx):
    split = ctx.get("scopes")
    return None if split is None else split.metrics_ms(
        ctx["steps"]).get("ef_compact_ms")
