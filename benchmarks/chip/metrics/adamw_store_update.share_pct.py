"""The fused AdamW kernel's device time over the step's device time
(the union of all operations) in the traced window, averaged over the
chips.  Layer: kernels."""
from benchmarks.chip import trace as tr

KERNEL = "adamw_store_update"


def read(ctx):
    names = {n for n in ctx.kernels if n.startswith(KERNEL)}
    kt = tr.op_time(ctx.trace, ctx.window, names)
    busy = tr.busy(ctx.trace, ctx.window)
    shares = [100.0 * kt[d] / busy[d] for d in busy if busy[d] > 0]
    if not shares or not any(kt.values()):
        return None
    return sum(shares) / len(shares)
