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


def matrix_runs(placements, shard_size: int, num_shards: int) -> np.ndarray:
    """(num_shards, R, 2) int32 table of each shard's matrix runs.

    The placements of rank >= 2, in offset order, merge into runs of
    adjacent matrices (a run ends where the next matrix does not start);
    row ``d`` holds those runs clipped to shard ``d``'s interval
    ``[d*S, (d+1)*S)`` as local ``[lo, hi)`` pairs in ``[0, S]``, padded
    with empty ``(0, 0)`` runs to the longest row.  Global offsets stay
    Python ints, so groups past 2^31 elements do not overflow."""
    runs: list[list[int]] = []
    for pl in sorted(placements, key=lambda p: p.offset):
        if len(pl.spec.shape) < 2:
            continue
        if runs and runs[-1][1] == pl.offset:
            runs[-1][1] = pl.end
        else:
            runs.append([pl.offset, pl.end])
    S = shard_size
    rows = [[(max(a, d * S) - d * S, min(b, (d + 1) * S) - d * S)
             for a, b in runs if a < (d + 1) * S and b > d * S]
            for d in range(num_shards)]
    table = np.zeros((num_shards, max(map(len, rows), default=0), 2),
                     np.int32)
    for d, row in enumerate(rows):
        table[d, :len(row)] = np.reshape(row, (-1, 2))
    return table


@jax.named_scope(spans.OPTIM_WD_MASK)
def matrix_mask_local(runtime, layout, local_shape):
    """(local_shape) 0/1 mask: 1 where the flat position belongs to a >=2-D
    tensor (weight-decay / Muon eligible).

    Built from this device's row of ``matrix_runs``: about 4 vector ops per
    run of adjacent matrices, in local coordinates (< S < 2^31), on the
    dense ``(S/128, 128)`` view of the shard."""
    S = layout.plan.shard_size
    sizes = dict(zip(runtime.mesh.axis_names, runtime.mesh.devices.shape))
    m = int(np.prod([sizes[a] for a in layout.fsdp_axes]))
    table = matrix_runs(layout.plan.placements, S, m)
    runs = jnp.asarray(table)[device_linear_index(runtime, layout)]
    # the ragged planner's g_coll makes S a multiple of 128; the baseline
    # planners' S need not be, and those shards stay one row
    lane = 128 if S % 128 == 0 else S
    view = (S // lane, lane)
    pos = (lax.broadcasted_iota(jnp.int32, view, 0) * lane
           + lax.broadcasted_iota(jnp.int32, view, 1))
    inside = jnp.zeros(view, bool)
    for k in range(table.shape[1]):
        inside = inside | ((pos >= runs[k, 0]) & (pos < runs[k, 1]))
    mask = inside.astype(jnp.float32).reshape(S)
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
