"""Share of the busy device time that the FSDP runtime takes: operations
under the program scopes ``fsdp.gather`` (all-gather and wire decode;
its transpose, the gradient reduce-scatter), ``fsdp.unpack`` (the flat
RaggedShard buffer to tensors; its transpose, gradients written back
into the flat buffer) and ``fsdp.grad_sync`` (replica psums, gradient
scaling, global norm), forward, backward and recomputed alike, averaged
over the chips.  Layer: FSDP runtime (``core/fsdp._ParamGetter``,
``core/store``, ``core/wire``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.share(ctx, scopes.in_any("fsdp.gather", "fsdp.unpack",
                                           "fsdp.grad_sync"))
