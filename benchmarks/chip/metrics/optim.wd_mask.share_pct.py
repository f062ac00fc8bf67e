"""Share of the busy device time under the program scope ``optim.wd_mask``
(the weight-decay mask ``optim/common.matrix_mask_local`` builds each
step; nested in ``optim.update``), averaged over the chips.  Layer:
optimizer (``optim/common.matrix_mask_local``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, scopes.in_any("optim.wd_mask"))
