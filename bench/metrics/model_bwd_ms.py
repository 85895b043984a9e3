"""model_bwd_ms: device time of the model's backward pass: ops in
``transpose(jvp(model))``, the forward that remat recomputes for it
included, in ms per step, averaged over the chips: the step's
class-``step`` ops split by the layers the program names
(``bench/scopes.py``).  The nine such metrics sum to ``step_xla_ms``.
None in a run that kept no split.
"""


def read(ctx):
    split = ctx.get("scopes")
    return None if split is None else split.metrics_ms(
        ctx["steps"]).get("model_bwd_ms")
