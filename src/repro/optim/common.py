"""Optimizer base utilities working on flat DBuffer shards.

Optimizers run *inside* shard_map on the device-local slice of each group
buffer, so every update is one group-fused elementwise pass (the DBuffer
batched-kernel claim of the paper).  Per-tensor behavior (weight decay only
on matrices, Muon only on 2D params) is recovered from the static plan via
position masks computed from the device's linear FSDP index.

Storage formats: ``params[name]`` is a ParamStore *state* (core.store) --
the flat buffer itself for fp32/bf16 stores, a codes/master/scales dict for
q8_block.  Every optimizer reads the fp32 weights through
``layout.store.master_f32`` (identity for fp32: the update graph stays
bitwise-identical to the pre-store runtime) and writes them back through
``layout.store.rebuild``, which requantizes codes/scales inside the same
fused update pass for quantized stores.  Optimizer *state* (m/v/moments) is
always master-shaped, independent of the store format.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import spans


def device_linear_index(runtime, layout):
    """This device's shard index within the group's FSDP axes (0..m-1)."""
    idx = 0
    sizes = dict(zip(runtime.mesh.axis_names, runtime.mesh.devices.shape))
    for a in layout.fsdp_axes:
        idx = idx * sizes[a] + lax.axis_index(a)
    return idx


@jax.named_scope(spans.OPTIM_WD_MASK)
def matrix_mask_local(runtime, layout, local_shape):
    """(local_shape) 0/1 mask: 1 where the flat position belongs to a >=2-D
    tensor (weight-decay / Muon eligible).  Computed from plan intervals and
    the device index; O(#tensors) vector ops.

    Global offsets can exceed int32 (multi-billion-element groups), so the
    comparison runs in (128-lane block, within-block) coordinates: block
    indices stay < total/128 < 2^31 for any realistic group."""
    S = layout.plan.shard_size  # multiple of LANE=128 by planner g_coll
    dev = device_linear_index(runtime, layout)
    blk = dev * (S // 128) + jnp.arange(S, dtype=jnp.int32) // 128
    within = jnp.arange(S, dtype=jnp.int32) % 128

    def ge(off: int):  # global_pos >= off
        ob, orem = off // 128, off % 128
        return (blk > ob) | ((blk == ob) & (within >= orem))

    mask = jnp.zeros((S,), jnp.float32)
    for pl in layout.plan.placements:
        if len(pl.spec.shape) >= 2:
            mask = jnp.where(ge(pl.offset) & ~ge(pl.end), 1.0, mask)
    # broadcast to (L, S) etc.
    while mask.ndim < len(local_shape):
        mask = mask[None]
    return jnp.broadcast_to(mask, local_shape)


class OptimizerBase:
    def __init__(self, cfg):
        self.cfg = cfg
        self.lr = cfg.learning_rate

    # state shape helpers ------------------------------------------------
    def _like_params(self, runtime, dtype=jnp.float32, div: int = 1):
        out = {}
        for name, lo in runtime.layouts.items():
            shape = lo.global_shape()
            shape = shape[:-1] + (shape[-1] // div,)
            out[name] = jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(runtime.mesh, lo.pspec())
            )
        return out

    def _zeros(self, runtime, dtype=jnp.float32, div: int = 1):
        shapes = self._like_params(runtime, dtype, div)
        return {
            k: jax.device_put(
                np.zeros(v.shape, v.dtype), v.sharding
            )
            for k, v in shapes.items()
        }

    # dry-run support: state as ShapeDtypeStructs (no allocation) ---------
    def state_shapes(self, runtime) -> dict:
        """{state_key: {group_name: ShapeDtypeStruct}}; every leaf is
        sharded with its group's pspec."""
        raise NotImplementedError

    def init(self, runtime):
        return jax.tree.map(
            lambda s: jax.device_put(np.zeros(s.shape, s.dtype), s.sharding),
            self.state_shapes(runtime),
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )

    def pspecs(self, runtime):
        return {
            key: {g: runtime.layouts[g].pspec() for g in sub}
            for key, sub in self.state_shapes(runtime).items()
        }

    def _param_pspecs(self, runtime):
        return {n: lo.pspec() for n, lo in runtime.layouts.items()}

    def schedule(self, step):
        warmup = 100.0
        return self.lr * jnp.minimum((step + 1.0) / warmup, 1.0)
