"""input_ms: device time of programs other than the step (the feed that
makes each batch), in ms per step, averaged over the chips."""


def read(ctx):
    t = ctx["summary"].seconds("other")
    return 1e3 * t / ctx["steps"] if t > 0 else None
