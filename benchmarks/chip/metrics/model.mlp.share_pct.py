"""Share of the busy device time under the program scope ``model.mlp``
(a block's feed-forward half: pre-norm, SwiGLU), forward, backward and
recomputed, averaged over the chips.  Layer: model
(``models/layers.mlp``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, scopes.in_any("model.mlp"))
