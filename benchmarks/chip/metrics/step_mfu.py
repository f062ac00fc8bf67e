"""Model FLOP/s utilization of the train step over the traced window:
the benchmark's count of the FLOPs a step requires (``flops.py``; no
recomputation) times the steps completed, over the window, the chips
and the bf16 peak of the ``device_kind``.  Layer: train step
(``core/fsdp.make_train_step``)."""


def read(ctx):
    if not ctx.steps:
        return None
    return 100.0 * ctx.flops_per_step * ctx.steps / (
        ctx.window_s * ctx.chips * ctx.peak["bf16_flops"])
