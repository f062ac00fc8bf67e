"""Share of the busy device time under the program scope ``model.attn``
(a block's attention half: pre-norm, q/k/v projections and biases, RoPE,
scores, softmax, output projection), forward, backward and recomputed,
averaged over the chips.  Layer: model (``models/layers.attention``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, scopes.in_any("model.attn"))
