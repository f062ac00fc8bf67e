"""Roofline share of the fused AdamW kernel (``kernels/fused_update.py``
through ``kernels/ops.adamw_store_update``).  It is bound by memory: the
least time is the bytes it must move (its operands ``w g m v mask`` and
results ``w m v``, from their shapes in the compiled HLO) over the HBM
peak; the share is that over the summed device time of its calls in the
trace, averaged over the chips."""

KERNEL = "adamw_store_update"


def read(ctx):
    names = {n: k for n, k in ctx.kernels.items() if n.startswith(KERNEL)}
    shares = []
    for d, events in ctx.trace.ops.items():
        lo, hi = ctx.window
        calls = [(n, e - s) for n, s, e in events
                 if n in names and lo <= s and e <= hi]
        t = sum(dt for _, dt in calls)
        if t > 0:
            nbytes = sum(names[n]["bytes"] for n, _ in calls)
            shares.append(100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / t)
    return sum(shares) / len(shares) if shares else None
