"""Share of the busy device time under the program scope
``model.head_loss`` (final norm, LM-head logits, cross-entropy, and
their backward), averaged over the chips.  Layer: model
(``lm_logits``, CE)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, scopes.in_any("model.head_loss"))
