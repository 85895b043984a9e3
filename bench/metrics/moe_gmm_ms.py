"""moe_gmm_ms: device time of the expert layers' grouped products, in ms
per step, averaged over the chips: the step's Mosaic kernels (class
``mosaic`` of ``bench/trace.py``).  In a cell whose error feedback runs
on the jnp path these are megablox's ``gmm`` (the forward products,
remat's recompute of them, the inputs' gradients) and ``tgmm`` (the
weights' gradients), and nothing else.

None where the step ran no Mosaic kernel, and None where the trace's
longest ops (the summary's ``top_ops``) show a Mosaic kernel of another
name: its time would be counted here.  A small one outside them cannot
be seen (the harness gives a reader the time of a class, not of each
kernel), so a change that brings another Mosaic kernel into such a cell
takes this metric out of it.
"""

KERNELS = ("gmm", "tgmm")        # megablox's instructions: gmm.6, tgmm.2


def seconds_per_step(ctx):
    """The grouped products' seconds a step, or None (see above)."""
    summary = ctx["summary"]
    for key, _ in summary.top_ops:
        cls, _, name = key.partition(":")
        if cls == "mosaic" and name.rsplit(".", 1)[0] not in KERNELS:
            return None
    t = summary.seconds("mosaic") / ctx["steps"]
    return t if t > 0 else None


def read(ctx):
    t = seconds_per_step(ctx)
    return None if t is None else 1e3 * t
