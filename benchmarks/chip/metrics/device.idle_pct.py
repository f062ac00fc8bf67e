"""Share of the traced window in which no operation runs on the device,
averaged over the cell's chips.  Layer: device (XLA:TPU)."""
from benchmarks.chip import trace as tr


def read(ctx):
    busy = tr.busy(ctx.trace, ctx.window)
    if not busy:
        return None
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean / ctx.window_s)
