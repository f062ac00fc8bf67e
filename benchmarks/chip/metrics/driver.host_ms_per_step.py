"""Host time per step outside the wait: batch placement plus step
dispatch, from the benchmark's ``bench.place`` and ``bench.dispatch``
spans in the traced window.  Layer: driver (``launch/train.train_loop``)."""


def read(ctx):
    lo, hi = ctx.window
    spent = sum(e - s for n, s, e in ctx.trace.spans
                if n in ("bench.place", "bench.dispatch") and lo <= s < hi)
    return 1e3 * spent / ctx.steps if ctx.steps else None
