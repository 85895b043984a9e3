"""step_xla_ms: device time of the step program's operations that are
neither Mosaic kernels nor collectives (the model's forward and
backward passes, the error feedback where it runs on the jnp path, the
optimizer, bucket packing and decode), in ms per step, averaged over
the chips."""


def read(ctx):
    t = ctx["summary"].seconds("step")
    return 1e3 * t / ctx["steps"] if t > 0 else None
