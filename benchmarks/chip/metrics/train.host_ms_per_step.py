"""Host time per step that the program's own spans name: ``train.batch``
(making and placing the batch) plus ``train.dispatch`` (enqueueing the
step), from ``launch/train.train_loop``'s spans in the traced window.
Layer: driver (``launch/train.train_loop``)."""
from benchmarks.chip import scopes


def read(ctx):
    ctx = scopes.context(ctx)
    lo, hi = ctx.window
    spent = [e - s for n, s, e in ctx.program_spans
             if n in ("train.batch", "train.dispatch") and lo <= s < hi]
    return 1e3 * sum(spent) / ctx.steps if spent and ctx.steps else None
