"""Share of the busy device time under the program scope ``model.embed``
(the token embedding lookup and, in the backward, its scatter-add into
the table), averaged over the chips.  Layer: model."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, scopes.in_any("model.embed"))
