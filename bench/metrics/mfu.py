"""mfu: the whole step's share of the chips' bf16 peak, in %.

Matmul FLOPs a training step requires (``bench/flops.py``: forward and
backward products, no recomputation) times the steps of the traced
window, over the window's wall time, the cell's chips and the peak of
their ``device_kind`` (``bench/peaks.py``).
"""


def read(ctx):
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    done = ctx["train_flops_per_step"] * ctx["steps"]
    return 100.0 * done / (ctx["window_s"] * peak)
