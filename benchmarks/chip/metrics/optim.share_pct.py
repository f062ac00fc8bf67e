"""Share of the busy device time under the program scope ``optim.update``
(the optimizer: weight-decay mask, fused AdamW kernel, any elementwise
work beside them), averaged over the chips.  Layer: optimizer
(``optim/adamw.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, scopes.in_any("optim.update"))
