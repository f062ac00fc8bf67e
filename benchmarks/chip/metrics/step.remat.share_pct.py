"""Share of the busy device time spent recomputing the forward in the
backward: operations whose ``op_name`` runs through
``checkpoint/rematted_computation`` (the remat of
``_ParamGetter.scan``), averaged over the chips.  Layer: train step
(``core/fsdp.make_train_step``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, lambda _, remat: remat, needs_scopes=False)
