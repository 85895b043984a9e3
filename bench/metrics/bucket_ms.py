"""bucket_ms: device time of bucket packing and unpacking, scopes
``bucket.pack`` and ``bucket.unpack``, in ms per step, averaged over the
chips: the step's class-``step`` ops split by the layers the program
names (``bench/scopes.py``).  The nine such metrics sum to
``step_xla_ms``.  None in a run that kept no split.
"""


def read(ctx):
    split = ctx.get("scopes")
    return None if split is None else split.metrics_ms(
        ctx["steps"]).get("bucket_ms")
