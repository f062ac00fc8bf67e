"""Names of the training step's work, as a profiler shows them.

Device scopes are ``jax.named_scope``s.  They change no computation: the
name lands in each compiled HLO instruction's ``op_name`` metadata, after
the pass (``jvp(...)`` forward, ``transpose(jvp(...))`` backward) and,
for work recomputed in the backward, after ``checkpoint/
rematted_computation``, so device time splits by program layer, pass and
recompute.  The four ``model.*`` scopes, the three ``fsdp.*`` scopes and
``optim.update`` never nest in one another; ``optim.wd_mask`` nests in
``optim.update``.

Host spans are ``jax.profiler`` annotations in ``launch.train.
train_loop``: ``TraceMe``s that cost nothing while no profiler is
attached, and that sit on the device trace's clock while one is.
"""

#: the token embedding lookup (``DecoderLM._embed_in``)
MODEL_EMBED = "model.embed"
#: a block's attention half: pre-norm, q/k/v projections and biases,
#: RoPE, scores, softmax, output projection, residual add
MODEL_ATTN = "model.attn"
#: a block's feed-forward half: pre-norm, SwiGLU (or the MoE), residual add
MODEL_MLP = "model.mlp"
#: final norm, LM-head logits and the cross-entropy (``DecoderLM.loss``)
MODEL_HEAD_LOSS = "model.head_loss"
#: all-gather and wire decode of a group's store state; its transpose is
#: the gradient reduce-scatter
FSDP_GATHER = "fsdp.gather"
#: the gathered flat RaggedShard buffer into tensors; its transpose
#: writes gradients back into the flat buffer
FSDP_UNPACK = "fsdp.unpack"
#: replica gradient psums, loss and weight psums, gradient scaling and
#: the global gradient norm (``FSDPRuntime.make_train_step``)
FSDP_GRAD_SYNC = "fsdp.grad_sync"
#: the optimizer's ``update``: masks, fused kernels, elementwise work
OPTIM_UPDATE = "optim.update"
#: the weight-decay / matrix mask (``optim.common.matrix_mask_local``)
OPTIM_WD_MASK = "optim.wd_mask"

DEVICE_SCOPES = (MODEL_EMBED, MODEL_ATTN, MODEL_MLP, MODEL_HEAD_LOSS,
                 FSDP_GATHER, FSDP_UNPACK, FSDP_GRAD_SYNC, OPTIM_UPDATE,
                 OPTIM_WD_MASK)

#: one iteration of ``train_loop`` (a ``StepTraceAnnotation``, which also
#: gives XProf its steps)
TRAIN_STEP = "train.step"
#: ``batch_for(i)``: making and placing the step's batch
TRAIN_BATCH = "train.batch"
#: the ``step_fn(...)`` call that enqueues the step on the device
TRAIN_DISPATCH = "train.dispatch"

HOST_SPANS = (TRAIN_STEP, TRAIN_BATCH, TRAIN_DISPATCH)
