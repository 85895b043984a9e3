"""idle_share: the share of the traced window, in %, in which no
operation ran on the device: 1 - (union of the device-op intervals) /
window, averaged over the cell's chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx["summary"].busy_s / ctx["window_s"])
