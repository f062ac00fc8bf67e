"""veScale-FSDP runtime: fully_shard-style API over RaggedShard + DBuffer.

``FSDPRuntime`` wraps a model (repro.models.*) for a mesh.  Its layout is
a consumed artifact, not a derivation: construction resolves (or is
handed) a ``core.policy.ShardingPlan`` -- per-group ``ShardingPolicy`` +
planner placements -- and builds group layouts from it.  The legacy
``ParallelConfig`` knobs and the ``schedule=``/``group_schedules=``
kwargs lower onto a ``PolicySet`` bitwise-neutrally; ``policies="auto"``
runs the cost-model planner; ``plan=`` replays an explicit (e.g.
checkpoint-restored) plan exactly.  Then:

  * each communication group's tensors are localized (outer TP/EP sharding
    composed per paper §4), planned (Algorithm 1), and backed by a DBuffer
    whose flat buffer is sharded over the group's FSDP mesh axes.  The
    *storage format* of that buffer is a ParamStore policy (core.store):
    fp32 master weights (default), bf16, or block-wise int8 codes+scales
    alongside an fp32 master shard (``param_store="q8_block"``, the paper's
    block-wise quantized training scenario);
  * the train step runs under shard_map.  The layer scan all-gathers one
    layer's store payload (bf16 flat buffer by default; int8 codes + scales
    for quantized stores, dequantized locally), unpacks zero-copy, and
    computes; ``jax.grad`` transposes the gather into a psum-scatter, which
    IS the ZeRO-3 gradient reduce-scatter -- targeting the store's
    trainable (master) buffer.  Remat re-gathers parameters in the backward
    pass, matching FSDP's backward re-allgather;
  * HSDP: on the multi-pod mesh the ``pod`` axis replicates parameters and
    grads are psum'd across pods (paper §6.1); ``pod_fsdp=True`` extends
    ZeRO-3 over pods instead;
  * the optimizer update is group-fused over the flat local shard (DBuffer
    group ops), with buffers donated for in-place semantics.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import spans
from ..compat import optimization_barrier, shard_map
from ..models.transformer import GroupDef
from .dbuffer import DBuffer
from .policy import PolicySet, ShardingPlan, make_plan
from .ragged import TensorSpec
from .schedule import CommSchedule
from .store import EF_KEY, ParamStore
from .wire import codec_reduce_scatter


# ---------------------------------------------------------------------------
# group layout resolution
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupLayout:
    name: str
    gdef: GroupDef
    local_specs: tuple[TensorSpec, ...]
    plan: Any               # GroupPlan
    buffer: DBuffer
    fsdp_axes: tuple[str, ...]
    fsdp_axis_sizes: tuple[int, ...]
    outer_axis: str | None     # TP/EP axis the buffer is additionally split on
    outer_size: int
    n_layers: int | None
    # axes the group is replicated on because its schedule said
    # sharded=False: no gather is emitted; grads are psum'd here instead
    grad_sync_axes: tuple[str, ...] = ()
    # storage format of the group's sharded buffer (what params[name] holds
    # and what the all-gather moves) -- see core.store.ParamStore
    store: ParamStore = ParamStore()

    @property
    def sharded_dim(self) -> int:
        return self.outer_size * self.plan.total

    def global_shape(self) -> tuple[int, ...]:
        d = (self.sharded_dim,)
        return (self.n_layers,) + d if self.n_layers else d

    def pspec(self) -> P:
        axes = ((self.outer_axis,) if self.outer_axis else ()) + self.fsdp_axes
        if not axes:
            entry = None  # unsharded (replicated) group
        else:
            entry = axes if len(axes) > 1 else axes[0]
        return P(None, entry) if self.n_layers else P(entry)


class FSDPRuntime:
    def __init__(self, model, mesh: Mesh, *, planner: str = "ragged",
                 compute_dtype=jnp.bfloat16, donate: bool = True,
                 scan_unroll: int = 1, schedule: CommSchedule | None = None,
                 group_schedules: Mapping[str, Any] | None = None,
                 policies=None, plan: ShardingPlan | None = None,
                 cost_model=None, verify: bool = False):
        self.model = model
        self.cfg = model.cfg
        self.mesh = mesh
        self.compute_dtype = compute_dtype
        self.donate = donate
        self.scan_unroll = scan_unroll  # cost-calibration dry runs unroll
        par = self.cfg.parallel
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        cdt = jnp.dtype(self.compute_dtype)

        # resolve the ShardingPlan the runtime consumes: an explicit plan,
        # a policies spec (PolicySet / ShardingPolicy / "auto" / ...), or
        # the legacy ParallelConfig knobs + schedule/group_schedules kwargs
        # lowered onto a PolicySet (bitwise-neutral -- the parity suites pin
        # the lowering down)
        if plan is not None:
            if (policies is not None or schedule is not None
                    or group_schedules is not None):
                raise ValueError(
                    "pass either plan= or policies=/schedule="
                    "/group_schedules=, not both")
            got = {a: int(s) for a, s in plan.axis_sizes.items()}
            if got != axis_sizes:
                raise ValueError(
                    f"plan was resolved for mesh axes {got}, runtime mesh "
                    f"has {axis_sizes}; re-plan for this mesh")
            if plan.compute_dtype != cdt.name:
                raise ValueError(
                    f"plan was resolved for compute dtype "
                    f"{plan.compute_dtype}, runtime uses {cdt.name}")
        else:
            if policies is None:
                policies = PolicySet.from_parallel_config(
                    par, schedule=schedule, group_schedules=group_schedules)
            elif schedule is not None or group_schedules is not None:
                raise ValueError(
                    "pass either policies= or schedule=/group_schedules=, "
                    "not both")
            plan = make_plan(model, mesh, policies, planner=planner,
                             compute_dtype=cdt, cost_model=cost_model)
        self.plan = plan
        self.planner_mode = plan.planner
        self.schedule = plan.base_schedule()
        self._group_scheds = plan.schedules()
        self.schedule.validate_for(cdt)
        for s in self._group_scheds.values():
            s.validate_for(cdt)

        self.has_pod = "pod" in axis_sizes
        self.tp = par.tp
        self.ep = par.ep
        self.tp_axis = "model" if par.tp > 1 else None
        self.ep_axis = "model" if par.ep > 1 else None

        gdefs = model.groups()
        if set(gdefs) != set(plan.groups):
            raise ValueError(
                f"plan groups {sorted(plan.groups)} do not match this "
                f"model's groups {sorted(gdefs)}")
        self.layouts: dict[str, GroupLayout] = {
            name: GroupLayout(
                name=name, gdef=gdefs[name], local_specs=e.local_specs,
                plan=e.plan, buffer=DBuffer(e.plan), fsdp_axes=e.fsdp_axes,
                fsdp_axis_sizes=e.fsdp_axis_sizes, outer_axis=e.outer_axis,
                outer_size=e.outer_size, n_layers=e.n_layers,
                grad_sync_axes=e.grad_sync_axes, store=e.store)
            for name, e in plan.groups.items()
        }

        self.batch_axes = tuple(
            a for a in (("pod",) if self.has_pod else ()) + par.batch_axes
            if a in axis_sizes
        )
        self.batch_size_divisor = int(
            np.prod([axis_sizes[a] for a in self.batch_axes])
        )

        if verify:
            # prove the plan's declared invariants against the traced step
            # (repro.analysis: abstract eval only, nothing compiles) before
            # handing the runtime out; raises VerificationError with the
            # full Violation report on failure
            from ..analysis import verify_runtime

            verify_runtime(self).raise_if_failed()

    # ------------------------------------------------------------------ #
    def sched_for(self, name: str) -> CommSchedule:
        """The (possibly group-overridden) schedule for one comm group."""
        return self._group_scheds.get(name, self.schedule)

    # ------------------------------------------------------------------ #
    # state construction
    # ------------------------------------------------------------------ #
    def param_shapes(self) -> dict[str, Any]:
        """Per-group param-state structure: a ShapeDtypeStruct for flat
        stores (fp32 -- the seed's format -- or bf16), a dict of structs
        (codes/master/scales) for quantized stores."""
        out = {}
        for name, lo in self.layouts.items():
            out[name] = lo.store.state_struct(
                lo.global_shape(), NamedSharding(self.mesh, lo.pspec()))
        return out

    @staticmethod
    def _init_tensor(spec: TensorSpec, seed: int, layer: int | None):
        """Deterministic per-tensor init: identical values regardless of how
        tensors are grouped/sharded (so FSDP == TP == HSDP numerics)."""
        import zlib

        rng = np.random.default_rng(
            [seed, zlib.crc32(spec.name.encode()),
             0 if layer is None else layer + 1]
        )
        if len(spec.shape) >= 2:
            fan_in = spec.shape[0]
            a = rng.normal(0, 1.0 / math.sqrt(max(fan_in, 1)),
                           size=spec.shape)
        elif any(t in spec.name for t in ("ln", "norm", "skip", "scale")):
            a = np.ones(spec.shape)
        else:
            a = np.zeros(spec.shape)
        return a.astype(np.float32)

    def init_params(self, seed: int = 0) -> dict[str, jax.Array]:
        """Host-side init (small/reduced models and examples; the dry run
        never calls this)."""
        params = {}
        for name, lo in self.layouts.items():
            layers = list(range(lo.n_layers)) if lo.n_layers else [None]
            flats = []
            for li in layers:
                packs = []
                for r in range(lo.outer_size):
                    arrays = {}
                    for full_spec in lo.gdef.specs:
                        a = self._init_tensor(full_spec, seed, li)
                        sd = lo.gdef.outer.get(full_spec.name)
                        if sd is not None:
                            a = np.split(a, lo.outer_size, axis=sd.dim)[r]
                        arrays[full_spec.name] = a
                    packs.append(lo.buffer.pack(arrays))
                flats.append(np.concatenate(packs))
            arr = np.stack(flats) if lo.n_layers else flats[0]
            sharding = NamedSharding(self.mesh, lo.pspec())
            params[name] = jax.tree.map(
                lambda a: jax.device_put(a, sharding),
                lo.store.create(arr))
        return params

    # ------------------------------------------------------------------ #
    # in-job elastic resharding (ROADMAP #4)
    # ------------------------------------------------------------------ #
    def replan(self, params, opt_state=None, *, mesh: Mesh | None = None,
               model=None, plan: ShardingPlan | None = None, policies=None,
               schedule=None, group_schedules=None, planner: str | None = None,
               optimizer=None):
        """Re-plan in place: a new mesh / policies / TP degree without a
        save/load round trip.  Returns ``(new_runtime, new_params,
        new_opt_state)`` (``new_opt_state`` is None unless ``opt_state``
        and ``optimizer`` are given).

        ``plan.diff`` (via ``policy.layout_changed_groups``) splits the
        groups: unchanged layout+store moves bitwise as raw shard bytes
        (EF history included); changed groups stream their fp32 master
        tensor-by-tensor through the extent map and rebuild their store
        state (codes requantized, EF re-zeroed) — the same parity classes
        as a checkpoint reshard, minus the disk."""
        from ..compat import tree_flatten_with_path, tree_unflatten
        from .policy import layout_changed_groups
        from .reshard import (GroupIndex, buffer_reader, buffer_writer,
                              stream_tensors)

        model = model if model is not None else self.model
        mesh = mesh if mesh is not None else self.mesh
        kwargs: dict[str, Any] = {}
        if plan is not None:
            kwargs["plan"] = plan
        elif policies is not None:
            kwargs["policies"] = policies
        elif schedule is not None or group_schedules is not None:
            kwargs["schedule"] = schedule
            kwargs["group_schedules"] = group_schedules
        elif model is self.model:
            # same model: keep this runtime's resolved per-group decisions
            kwargs["policies"] = self.plan.policy_set()
        # else: a new model (e.g. changed TP degree) lowers its own
        # ParallelConfig knobs
        new_rt = FSDPRuntime(
            model, mesh, planner=planner or self.planner_mode,
            compute_dtype=self.compute_dtype, donate=self.donate,
            scan_unroll=self.scan_unroll, **kwargs)

        changed = layout_changed_groups(self.plan, new_rt.plan)
        old_idx = {n: GroupIndex.from_layout(lo)
                   for n, lo in self.layouts.items()}
        tensor_src = {t: n for n, lo in self.layouts.items()
                      for t in lo.plan.names}
        # lazily-pulled host masters of changed source groups (one at a
        # time would be even leaner, but group granularity matches the
        # device_put batching below)
        masters: dict[str, np.ndarray] = {}

        def src_master(gname: str) -> np.ndarray:
            m = masters.get(gname)
            if m is None:
                state = params[gname]
                if isinstance(state, dict):
                    m = np.asarray(state["master"], np.float32)
                else:
                    m = np.asarray(
                        jnp.asarray(state).astype(jnp.float32))
                masters[gname] = m
            return m

        new_params = {}
        for name, lo in new_rt.layouts.items():
            sharding = NamedSharding(new_rt.mesh, lo.pspec())
            if name in self.layouts and name not in changed:
                new_params[name] = jax.tree.map(
                    lambda a: jax.device_put(np.asarray(a), sharding),
                    params[name])
                continue
            dst = GroupIndex.from_layout(lo)
            master = np.zeros(lo.global_shape(), np.float32)
            write = buffer_writer(master, dst.num_rows)

            def lookup(tname):
                g = tensor_src.get(tname)
                if g is None:
                    raise ValueError(
                        f"tensor {tname!r} (group {name!r}) does not exist "
                        f"in the current runtime; replan cannot invent "
                        f"parameters")
                return old_idx[g], buffer_reader(src_master(g),
                                                 old_idx[g].num_rows)

            stream_tensors(dst, write, lookup)
            new_params[name] = jax.tree.map(
                lambda a: jax.device_put(a, sharding),
                lo.store.create(master))

        if opt_state is None:
            return new_rt, new_params, None
        if optimizer is None:
            raise ValueError(
                "replan(opt_state=...) needs optimizer= to shape the new "
                "state tree")
        old_flat, _ = tree_flatten_with_path(opt_state)
        old_by_path = {
            tuple(getattr(p, "key", str(p)) for p in kp): v
            for kp, v in old_flat}
        like_flat, like_tree = tree_flatten_with_path(
            optimizer.state_shapes(new_rt))
        moved = []
        for kp, like in like_flat:
            keys = tuple(getattr(p, "key", str(p)) for p in kp)
            moved.append(jax.device_put(
                self._replan_opt_leaf(new_rt, keys, like, old_by_path,
                                      old_idx, tensor_src, changed),
                like.sharding))
        return new_rt, new_params, tree_unflatten(like_tree, moved)

    def _replan_opt_leaf(self, new_rt, keys, like, old_by_path, old_idx,
                         tensor_src, changed):
        from ..checkpoint.ckpt import _classify_opt_leaf
        from .reshard import GroupIndex, buffer_reader, buffer_writer, \
            copy_tensor

        pathname = "/".join(keys)
        kind, g_new, div = _classify_opt_leaf(new_rt, keys, like.shape)
        if kind != "buffer":
            old = old_by_path.get(keys)
            if old is None:
                raise ValueError(
                    f"optimizer state leaf {pathname!r} has no counterpart "
                    f"in the current state")
            a = np.asarray(old)
            if kind == "factor":
                # unpad to the true layer count, repad for the new plan
                L = self.layouts[g_new].n_layers
                if a.shape[1:] != like.shape[1:] or like.shape[0] < L:
                    raise ValueError(
                        f"optimizer state {pathname!r}: factor shape "
                        f"{a.shape} incompatible with {tuple(like.shape)}")
                out = np.zeros(like.shape, a.dtype)
                out[:L] = a[:L]
                return out
            if tuple(a.shape) != tuple(like.shape):
                raise ValueError(
                    f"optimizer state {pathname!r}: shape {a.shape} != "
                    f"expected {tuple(like.shape)}")
            return a
        lo = new_rt.layouts[g_new]
        old = old_by_path.get(keys)
        if g_new not in changed and old is not None \
                and tuple(old.shape) == tuple(like.shape):
            return np.asarray(old)
        dst = GroupIndex.from_layout(lo)
        dest = None
        aligned = div > 1 or jnp.dtype(like.dtype).kind in "iu"
        for name in lo.plan.names:
            g_old = tensor_src.get(name)
            src = old_by_path.get(keys[:-1] + (g_old,)) \
                if g_old is not None else None
            if src is None:
                raise ValueError(
                    f"optimizer state {pathname!r}: no source buffer for "
                    f"tensor {name!r} (old group {g_old!r})")
            src = np.asarray(src)
            s_idx = old_idx[g_old]
            src_div = (self.layouts[g_old].global_shape()[-1]
                       // src.shape[-1])
            if src_div != div:
                raise ValueError(
                    f"optimizer state {pathname!r}: block granularity "
                    f"changed ({src_div} -> {div}); 8-bit optimizer state "
                    f"cannot be resharded across it")
            if dest is None:
                dest = np.zeros(like.shape, src.dtype)
            if (s_idx.n_layers or 0) != (lo.n_layers or 0):
                raise ValueError(
                    f"optimizer state {pathname!r}: layer count changed "
                    f"for {name!r} ({s_idx.n_layers} -> {lo.n_layers})")
            read = buffer_reader(src, s_idx.num_rows)
            write = buffer_writer(dest, dst.num_rows)
            for li in (range(lo.n_layers) if lo.n_layers else [None]):
                copy_tensor(s_idx, dst, name, read, write,
                            layer=li, div=div, aligned=aligned)
        return np.asarray(
            jnp.asarray(dest).astype(like.dtype)) \
            if jnp.dtype(dest.dtype) != jnp.dtype(like.dtype) else dest

    # ------------------------------------------------------------------ #
    # the ParamGetter handed to model code inside shard_map
    # ------------------------------------------------------------------ #
    def _getter(self, local_bufs: Mapping[str, jax.Array], remat: bool = True,
                defer_ef: bool = False, quant_matmul: bool = False):
        return _ParamGetter(self, local_bufs, remat, defer_ef=defer_ef,
                            quant_matmul=quant_matmul)

    # specs for shard_map (a pspec per state leaf; scales shard like the
    # buffer because S % block == 0)
    def _param_specs(self) -> dict[str, Any]:
        return {n: lo.store.state_pspecs(lo.pspec())
                for n, lo in self.layouts.items()}

    def _usable_batch_axes(self, batch: int) -> tuple[str, ...]:
        """Longest prefix of batch axes that evenly divides ``batch`` --
        smaller global batches shard over fewer axes and replicate on the
        rest (e.g. decode_32k batch=128 on a 16x16 mesh -> data only)."""
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        usable = []
        rem = batch
        for a in self.batch_axes:
            if rem % sizes[a] == 0 and rem >= sizes[a]:
                usable.append(a)
                rem //= sizes[a]
        return tuple(usable)

    def batch_pspec(self, batch_tree) -> Any:
        def spec_for(leaf):
            usable = self._usable_batch_axes(leaf.shape[0]) if leaf.ndim else ()
            if usable:
                entry = usable if len(usable) > 1 else usable[0]
                return P(entry, *([None] * (leaf.ndim - 1)))
            return P(*([None] * leaf.ndim))

        return jax.tree.map(spec_for, batch_tree)

    # ------------------------------------------------------------------ #
    # train step
    # ------------------------------------------------------------------ #
    def make_train_step(self, optimizer) -> Callable:
        """optimizer: repro.optim.* object with init(layouts, params) and
        update(runtime, params, grads, state, step).

        The step's metrics are ``loss``, ``tokens`` and ``grad_norm``; a
        model that ``reports_routing`` adds ``routing``: ``experts`` and
        ``kept``, each (moe_layers, tokens, top_k) in the batch's token
        order, from the model's loss report."""
        par = self.cfg.parallel
        pspecs = self._param_specs()
        routed = getattr(self.model, "reports_routing", False)

        # groups whose reduce wire runs error feedback: their trainable
        # tree carries the residual, whose "gradient" is the updated
        # residual (core.wire EF primitives) -- split out of the grad tree
        # before loss scaling / replica psums, re-attached after the
        # optimizer update
        ef_groups = tuple(n for n, lo in self.layouts.items()
                          if lo.store.has_ef)
        # Gradient accumulation composes with the quantized reduce wire via
        # DEFERRED error feedback: the per-microbatch backward performs no
        # collective and no encode (core.wire's *_defer_ef primitives
        # return the raw fp32 cotangent as the residual slot's cotangent),
        # the scan accumulates sum(ct), and ONE codec_reduce_scatter at the
        # accumulation boundary applies the residual, encodes, and routes --
        # identical wire numerics and residual semantics to a single batch
        # of the same total size (encoding per microbatch would quantize
        # partial sums ``micro`` times and corrupt the EF history).
        for n in ef_groups:
            # groups whose grads are additionally psum'd over replica axes
            # (_reduce_grads: HSDP cross-pod, TP-replicated) would compute
            # a DIFFERENT residual per replica -- violating the state's
            # declared replication on those axes and corrupting EF through
            # a checkpoint (which saves one replica).  Quantized replica
            # reductions are a ROADMAP item; reject the combination.
            lo = self.layouts[n]
            replica = []
            if lo.gdef.replicated_over_model and self.tp > 1:
                replica.append("model")
            if (self.has_pod and "pod" not in lo.fsdp_axes
                    and "pod" not in lo.grad_sync_axes):
                replica.append("pod")
            if replica:
                raise ValueError(
                    f"reduce_wire='q8_block' on group {n!r} is unsupported "
                    f"with replica gradient axes {replica}: the error-"
                    f"feedback residual would diverge across replicas "
                    f"(quantized replica reductions are future work; use a "
                    f"cast reduce wire for this group)")

        def split_ef(raw):
            """(master grads, updated EF residuals) from the raw grad tree
            of ``trainable`` -- residuals must not see grad scaling,
            replica psums, or the grad-norm."""
            grads, efs = {}, {}
            for n, g in raw.items():
                if n in ef_groups:
                    grads[n] = g["master"]
                    efs[n] = g[EF_KEY]
                else:
                    grads[n] = g
            return grads, efs

        def step_fn(params, opt_state, step, batch):
            def sharded(params, opt_state, step, batch):
                # split each group's store state into the differentiable
                # part (the master/storage buffer the grads target, plus
                # the reduce-wire EF residual when one exists) and the
                # frozen payload (q8 codes/scales, closed over as
                # constants).  For fp32 stores trainable IS the params dict,
                # so the autodiff graph is unchanged from the seed.
                trainable = {n: self.layouts[n].store.trainable(params[n])
                             for n in params}
                frozen = {n: self.layouts[n].store.frozen(params[n])
                          for n in params}

                # clamp accumulation to a divisor of the local batch (the
                # multi-pod mesh halves the per-device batch vs single-pod)
                b_loc = jax.tree.leaves(batch)[0].shape[0]
                micro = par.microbatches
                while b_loc % micro:
                    micro -= 1
                # EF groups defer the quantized reduce-scatter to the
                # accumulation boundary when accumulating (micro == 1 keeps
                # the eager path, bit for bit)
                defer = bool(ef_groups) and micro > 1

                def loss_of(tr, mb):
                    bufs = {n: self.layouts[n].store.combine(tr[n], frozen[n])
                            for n in tr}
                    pg = self._getter(bufs, defer_ef=defer)
                    nll, w, report = self.model.loss(pg, mb)
                    return nll, (w, report)

                if micro > 1:
                    def micro_body(acc, mb):
                        grads, nll_a, w_a = acc
                        (nll, (w, report)), g = jax.value_and_grad(
                            loss_of, has_aux=True)(trainable, mb)
                        grads = jax.tree.map(jnp.add, grads, g)
                        return (grads, nll_a + nll, w_a + w), report

                    mbs = jax.tree.map(
                        lambda t: t.reshape((micro, t.shape[0] // micro)
                                            + t.shape[1:]), batch)
                    zero = jax.tree.map(jnp.zeros_like, trainable)
                    (grads, nll, w), reports = lax.scan(
                        micro_body, (zero, 0.0, 0.0), mbs)
                    # (micro, layers, tokens, ...) -> (layers, micro *
                    # tokens, ...): microbatches hold consecutive rows
                    report = jax.tree.map(
                        lambda r: jnp.moveaxis(r, 0, 1).reshape(
                            (r.shape[1], -1) + r.shape[3:]), reports)
                    if defer:
                        grads = dict(grads)
                        cd = jnp.dtype(self.compute_dtype)
                        for n in ef_groups:
                            # the accumulation boundary: sum(ct) rode the
                            # grad tree's EF slot (master slot held zeros);
                            # apply the residual, encode once, reduce-
                            # scatter -- exactly the eager EF backward on
                            # the accumulated cotangent
                            lo = self.layouts[n]
                            sched = self.sched_for(n)
                            rcodec = sched.reduce_codec(cd, lo.store.block)
                            pdt = (jnp.dtype(jnp.float32)
                                   if lo.store.quantized
                                   else lo.store.storage_dtype)

                            @jax.named_scope(spans.FSDP_GRAD_SYNC)
                            def rs(ct1, ef1, lo=lo, sched=sched,
                                   rcodec=rcodec, pdt=pdt):
                                return codec_reduce_scatter(
                                    ct1, ef1, rcodec, lo.fsdp_axes,
                                    lo.fsdp_axis_sizes, sched.gather_mode,
                                    sched.reduce_mode, pdt,
                                    sched.ring_chunk_elems)

                            sum_ct = grads[n][EF_KEY]
                            ef0 = trainable[n][EF_KEY]
                            if sum_ct.ndim > 1:
                                # layered group: one reduce-scatter per
                                # layer (collectives-in-scan, the same
                                # structure the layer gather runs)
                                _, (shard, new_ef) = lax.scan(
                                    lambda c, a: (c, rs(*a)), None,
                                    (sum_ct, ef0))
                            else:
                                shard, new_ef = rs(sum_ct, ef0)
                            grads[n] = {"master": shard, EF_KEY: new_ef}
                else:
                    (nll, (w, report)), grads = jax.value_and_grad(
                        loss_of, has_aux=True)(trainable, batch)

                # the EF residuals ride back through the grad tree (their
                # cotangent IS the updated residual); peel them off before
                # any scaling -- residuals live in unscaled cotangent units
                grads, new_efs = split_ef(grads)

                # cross-device normalization
                with jax.named_scope(spans.FSDP_GRAD_SYNC):
                    nll_g = (lax.psum(nll, self.batch_axes)
                             if self.batch_axes else nll)
                    w_g = lax.psum(w, self.batch_axes) if self.batch_axes else w
                    grads = self._reduce_grads(grads)
                    scale = 1.0 / jnp.maximum(w_g, 1.0)
                    grads = jax.tree.map(lambda g: g * scale, grads)
                with jax.named_scope(spans.OPTIM_UPDATE):
                    new_params, new_opt = optimizer.update(
                        self, params, grads, opt_state, step)
                for n in ef_groups:
                    # optimizers are EF-oblivious (rebuild returns the core
                    # state); re-attach the updated residual here
                    new_params[n] = self.layouts[n].store.attach_ef(
                        new_params[n], new_efs[n])
                with jax.named_scope(spans.FSDP_GRAD_SYNC):
                    metrics = {
                        "loss": nll_g / jnp.maximum(w_g, 1.0),
                        "tokens": w_g,
                        "grad_norm": _global_norm(self, grads),
                    }
                if routed:
                    metrics["routing"] = report["routing"]
                return new_params, new_opt, metrics

            opt_specs = optimizer.pspecs(self)
            batch_specs = self.batch_pspec(batch)
            metric_specs = {"loss": P(), "tokens": P(), "grad_norm": P()}
            if routed:
                # each device's tokens, its rows of the batch, in order
                rows = batch_specs["tokens"][0]
                metric_specs["routing"] = {"experts": P(None, rows, None),
                                           "kept": P(None, rows, None)}
            fn = shard_map(
                sharded, mesh=self.mesh,
                in_specs=(pspecs, opt_specs, P(), batch_specs),
                out_specs=(pspecs, opt_specs, metric_specs),
            )
            new_params, new_opt, metrics = fn(params, opt_state, step, batch)
            return new_params, new_opt, step + 1, metrics

        donate = (0, 1) if self.donate else ()
        return jax.jit(step_fn, donate_argnums=donate)

    def _reduce_grads(self, grads):
        """Extra reductions beyond the autodiff reduce-scatter: replicated
        groups psum over 'model'; schedule-unsharded groups psum over their
        would-be FSDP axes; HSDP psums over 'pod'.

        When the group's schedule pins a reduce dtype or wire, these
        replica psums accumulate in the resolved accum dtype (the fp32
        option matters for the HSDP cross-pod sum at paper scale; a
        quantized reduce wire accumulates in fp32, and its replica psums
        stay full-precision -- only the reduce-scatter is quantized); with
        neither set they run in whatever dtype the grads arrive in, which
        preserves the seed trajectory."""
        cd = jnp.dtype(self.compute_dtype)
        out = {}
        for name, g in grads.items():
            lo = self.layouts[name]
            sched = self.sched_for(name)
            pinned = (sched.reduce_dtype is not None
                      or sched.reduce_wire is not None)
            ad = sched.accum_dtype(cd) if pinned else jnp.dtype(g.dtype)

            def _psum(v, axes, ad=ad):
                if ad != v.dtype:
                    return lax.psum(v.astype(ad), axes).astype(v.dtype)
                return lax.psum(v, axes)

            if lo.gdef.replicated_over_model and self.tp > 1:
                g = _psum(g, "model")
            if lo.grad_sync_axes:
                g = _psum(g, lo.grad_sync_axes)
            if (self.has_pod and "pod" not in lo.fsdp_axes
                    and "pod" not in lo.grad_sync_axes):
                # HSDP cross-pod psum -- unless the group is schedule-
                # unsharded on a pod_fsdp mesh, where grad_sync_axes
                # already covered "pod"
                g = _psum(g, "pod")
            out[name] = g
        return out

    # ------------------------------------------------------------------ #
    def gathered_peak_bytes(self) -> int:
        """Analytic peak of simultaneously-live gathered layer buffers in
        the training step -- the quantity the two-slot prefetch bounds:
        2 slots with prefetch, 1 without, +1 for the split-out last layer,
        or every layer when reshard_after_forward=False."""
        cd = jnp.dtype(self.compute_dtype)
        per_layer, n = 0, 0
        for name, lo in self.layouts.items():
            if lo.n_layers and lo.fsdp_axes:
                # the gather runs over fsdp_axes only: the outer (TP/EP)
                # shard stays local, so the per-device gathered buffer is
                # plan.total elements, not sharded_dim
                per_layer += lo.plan.total * cd.itemsize
                n = max(n, lo.n_layers)
        if not n:
            return 0
        if not self.schedule.reshard_after_forward:
            slots = n
        else:
            plan = self.schedule.plan_layers(n, remat=True)
            # no main-scan slot when the main scan is empty (n == 1 with
            # keep_last_gathered: only the split-out layer is ever live)
            main_slots = (2 if plan.prefetch else 1) if plan.main else 0
            slots = main_slots + int(plan.split_last)
        return per_layer * slots

    def gather_wire_bytes(self) -> int:
        """Analytic bytes the parameter all-gathers of ONE forward pass put
        on the wire, per gathered copy: the quantity the q8_block store cuts
        ~4x vs an fp32 wire (codes are 1 byte/element + 4 bytes per block of
        scales vs 4 bytes/element).  Schedule-unsharded and single-group
        replicated buffers move nothing; backward re-gathers (remat) and
        the (m-1)/m ring discount apply uniformly across formats, so they
        are deliberately left out of the ratio.  Delegates to the resolved
        ``ShardingPlan`` (same accounting, now a plan-level prediction
        available before a runtime exists)."""
        return self.plan.gather_wire_bytes()

    def reduce_wire_bytes(self) -> int:
        """Analytic bytes ONE gradient reduce-scatter pass puts on the
        wire, per reduced copy, in each group's reduce WireCodec -- the
        mirror of ``gather_wire_bytes`` (the q8_block gradient wire cuts
        this ~4x vs an fp32 reduce).  Delegates to the plan."""
        return self.plan.reduce_wire_bytes()

    # ------------------------------------------------------------------ #
    # serving steps (ZeRO-3 inference: per-layer gather, sharded at rest)
    # ------------------------------------------------------------------ #
    def cache_pspec(self, cache_tree, batch: int) -> Any:
        """Cache sharding: batch dim (declared by the model via
        ``cache_batch_dims`` -- size-based guessing collides when
        n_layers == batch) over the usable batch axes; with TP, KV head dims
        (== tp) over "model"."""
        usable = list(self._usable_batch_axes(batch))
        bdims = self.model.cache_batch_dims()

        def spec_for(leaf, bdim):
            nd = leaf.ndim
            entries = [None] * nd
            if usable and leaf.shape[bdim] == batch:
                entries[bdim] = (
                    tuple(usable) if len(usable) > 1 else usable[0])
            if self.tp > 1 and nd >= 5:
                # KV leaves: head dim (== tp) sharded over "model"
                for hdim in range(nd):
                    if entries[hdim] is None and leaf.shape[hdim] == self.tp:
                        entries[hdim] = "model"
                        break
            return P(*entries)

        return jax.tree.map(spec_for, cache_tree, bdims)

    def make_prefill_step(self):
        pspecs = self._param_specs()

        def step_fn(params, batch, cache):
            bsz = batch["tokens"].shape[0]
            cspec = self.cache_pspec(cache, bsz)

            def sharded(params, batch, cache):
                pg = self._getter(
                    params, remat=False,
                    quant_matmul=self.schedule.serve_quant_matmul)
                return self.model.prefill(pg, batch, cache)

            fn = shard_map(
                sharded, mesh=self.mesh,
                in_specs=(pspecs, self.batch_pspec(batch), cspec),
                out_specs=(self.batch_pspec(
                    {"tokens": jax.ShapeDtypeStruct((bsz, 1, 1), jnp.float32)}
                )["tokens"], cspec),
            )
            return fn(params, batch, cache)

        return jax.jit(step_fn)

    def make_decode_step(self):
        pspecs = self._param_specs()

        def step_fn(params, batch, cache, index):
            bsz = batch["tokens"].shape[0]
            cspec = self.cache_pspec(cache, bsz)
            # scalar position, or per-row (B,) positions sharded with batch
            idx_spec = (P() if jnp.ndim(index) == 0
                        else self.batch_pspec({"i": index})["i"])

            def sharded(params, batch, cache, index):
                pg = self._getter(
                    params, remat=False,
                    quant_matmul=self.schedule.serve_quant_matmul)
                return self.model.decode(pg, batch, cache, index)

            fn = shard_map(
                sharded, mesh=self.mesh,
                in_specs=(pspecs, self.batch_pspec(batch), cspec, idx_spec),
                out_specs=(self.batch_pspec(
                    {"tokens": jax.ShapeDtypeStruct((bsz, 1, 1), jnp.float32)}
                )["tokens"], cspec),
            )
            return fn(params, batch, cache, index)

        return jax.jit(step_fn, donate_argnums=(2,))


def _is_arr(x):
    return hasattr(x, "shape")


def _global_norm(runtime, grads):
    sq = 0.0
    for name, g in grads.items():
        lo = runtime.layouts[name]
        s = jnp.sum(g.astype(jnp.float32) ** 2)
        axes = lo.fsdp_axes + ((lo.outer_axis,) if lo.outer_axis else ())
        s = lax.psum(s, axes) if axes else s
        sq = sq + s
    return jnp.sqrt(sq)


# ---------------------------------------------------------------------------
# ParamGetter: gather + zero-copy unpack, layer scan driven by CommSchedule
# ---------------------------------------------------------------------------

class _ParamGetter:
    def __init__(self, runtime: FSDPRuntime, bufs, remat: bool,
                 defer_ef: bool = False, quant_matmul: bool = False):
        self.rt = runtime
        self.bufs = bufs
        self.remat = remat
        self.defer_ef = defer_ef
        # serve-only: keep eligible q8_block layer weights as int8
        # QuantTensors (ops.q8_matmul) instead of dequantizing the gather
        self.quant_matmul = quant_matmul
        self.schedule = runtime.schedule
        self.tp_axis = runtime.tp_axis
        self.ep_axis = runtime.ep_axis
        self.compute_dtype = runtime.compute_dtype

    def _gather_flat(self, name: str, local) -> jax.Array:
        """All-gather one group's store state per its (possibly
        group-overridden) schedule -- gather mode, wire/reduce dtypes, and
        storage format (backward = the ZeRO-3 gradient reduce-scatter onto
        the store's trainable buffer).  ``local`` is the device-local state:
        a flat slice for fp32/bf16 stores, a codes/master/scales dict for
        q8_block (the quantized wire)."""
        lo = self.rt.layouts[name]
        with jax.named_scope(spans.FSDP_GATHER):
            return lo.store.gather(
                local, lo.fsdp_axes, lo.fsdp_axis_sizes,
                self.rt.sched_for(name), self.rt.compute_dtype,
                defer_ef=self.defer_ef and lo.store.has_ef)

    def _quant_group(self, name: str) -> bool:
        return self.quant_matmul and self.rt.layouts[name].store.quantized

    def _gather_unpack(self, name: str, local: jax.Array):
        flat = self._gather_flat(name, local)
        with jax.named_scope(spans.FSDP_UNPACK):
            return self.rt.layouts[name].buffer.unpack(flat)

    def globals(self, group: str) -> dict[str, jax.Array]:
        return self._gather_unpack(group, self.bufs[group])

    def scan(self, groups, body, carry, xs=None):
        """FSDP layer scan.  The CommSchedule controls gather prefetching,
        whether gathered params are resharded after forward, and whether
        the last layer's gathered params stay live into backward.  The
        small-``n_layers`` fallbacks are resolved explicitly by
        ``CommSchedule.plan_layers`` (see ``LayerPlan``).

        Remat structure: activation rematerialization (``self.remat``) and
        parameter resharding (``schedule.reshard_after_forward``) are
        orthogonal.  Resharding puts the gather *inside* the checkpointed
        region (backward re-gathers = ZeRO-3); with resharding off, the
        gather moves outside so the gathered buffer is saved as a residual
        while layer activations are still rematted.

        Prefetch runs the main scan over layer *pairs* with a two-slot
        double buffer: slot ``i % 2`` holds layer ``i``'s gathered params,
        and both slots' gathers are issued before either layer's compute,
        so the odd slot's gather overlaps the even layer's compute.  The
        gathered buffers live only inside the (checkpointed) pair body --
        never in the scan carry -- so backward re-gathers each pair and
        peak gathered memory is two layer buffers regardless of depth.
        (Threading the next layer's gathered buffer through the
        checkpointed carry, as the first cut did, made it a per-step scan
        residual: backward retained one gathered buffer per layer.)"""
        sched = self.schedule
        stacks = tuple(self.bufs[g] for g in groups)
        n = self.rt.layouts[groups[0]].n_layers
        remat = self.remat
        reshard = sched.reshard_after_forward
        plan = sched.plan_layers(n, remat)

        def gather_layer(layer_bufs):
            out = []
            for g, lb in zip(groups, layer_bufs):
                if self._quant_group(g):
                    # serve quant mode: move the wire payload, defer the
                    # dequantize decision to unpack_quant (eligible 2-D
                    # weights never dequantize -- ops.q8_matmul)
                    lo = self.rt.layouts[g]
                    with jax.named_scope(spans.FSDP_GATHER):
                        out.append(lo.store.gather_payload(
                            lb, lo.fsdp_axes, lo.fsdp_axis_sizes,
                            self.rt.sched_for(g)))
                else:
                    out.append(self._gather_flat(g, lb))
            return tuple(out)

        @jax.named_scope(spans.FSDP_UNPACK)
        def unpack_all(gathered):
            p = {}
            for g, gb in zip(groups, gathered):
                lo = self.rt.layouts[g]
                if self._quant_group(g):
                    p.update(lo.buffer.unpack_quant(
                        gb, lo.store.block, self.compute_dtype))
                else:
                    p.update(lo.buffer.unpack(gb))
            return p

        def compute(gathered, c, user_xs):
            return body(unpack_all(gathered), c, user_xs)

        # activation-only remat: gathered buffers enter as checkpoint
        # inputs, so they are saved into backward (no re-gather)
        # what the model keeps through the checkpoint (a routed model: its
        # routing); None recomputes everything
        policy = getattr(self.rt.model, "remat_policy", None)
        inner = (jax.checkpoint(compute, policy=policy)
                 if remat and not reshard else compute)

        def slices(lo, hi):
            # stacks entries are store states (arrays or code/scale trees)
            return (tuple(jax.tree.map(lambda t: t[lo:hi], s)
                          for s in stacks),
                    jax.tree.map(lambda t: t[lo:hi], xs))

        def seq_scan(carry, lo, hi):
            """Sequential layers [lo, hi): gather inside the checkpointed
            body, so backward re-gathers (ZeRO-3)."""
            def scan_body(c, scan_xs):
                layer_bufs, user_xs = scan_xs
                return inner(gather_layer(layer_bufs), c, user_xs)

            if remat and reshard:
                scan_body = jax.checkpoint(scan_body, policy=policy)
            length = hi - lo
            return lax.scan(scan_body, carry, slices(lo, hi), length=length,
                            unroll=max(1, min(self.rt.scan_unroll, length)))

        ys_parts = []
        if plan.prefetch:
            k = 2 * plan.pairs

            def to_pairs(t):
                return t[:k].reshape((plan.pairs, 2) + t.shape[1:])

            pair_bufs = tuple(jax.tree.map(to_pairs, s) for s in stacks)
            pair_xs = jax.tree.map(to_pairs, xs)

            def pair_body(c, scan_xs):
                bufs2, xs2 = scan_xs
                # two-slot double buffer: issue both slots' gathers before
                # either layer's compute (slot 1 overlaps slot 0's compute)
                g0 = gather_layer(tuple(
                    jax.tree.map(lambda t: t[0], b) for b in bufs2))
                g1 = gather_layer(tuple(
                    jax.tree.map(lambda t: t[1], b) for b in bufs2))
                # pin the two-slot issue order explicitly: both slots'
                # gathered buffers materialize together before either
                # layer's compute.  Because remat replays this barrier, the
                # *backward* re-gathers are issued as a pair too -- the
                # issue order is in the jaxpr (regression-tested), not left
                # to XLA's scheduler.  The barrier is the identity, so
                # bitwise parity with the sequential schedule holds.
                g0, g1 = optimization_barrier((g0, g1))
                c, y0 = inner(g0, c, jax.tree.map(lambda t: t[0], xs2))
                # materialize the carry at the layer seam exactly as a
                # per-layer scan-iteration boundary would (bitwise parity
                # with the sequential schedule, forward and backward)
                c = optimization_barrier(c)
                c, y1 = inner(g1, c, jax.tree.map(lambda t: t[1], xs2))
                return c, (y0, y1)

            if remat and reshard:
                pair_body = jax.checkpoint(pair_body, policy=policy)
            carry, (ys0, ys1) = lax.scan(
                pair_body, carry, (pair_bufs, pair_xs), length=plan.pairs,
                unroll=max(1, min(self.rt.scan_unroll, plan.pairs)))
            ys_parts.append(jax.tree.map(
                lambda a, b: jnp.stack([a, b], axis=1).reshape(
                    (k,) + a.shape[1:]), ys0, ys1))
            if plan.tail:
                carry, y_tail = seq_scan(carry, k, plan.main)
                ys_parts.append(y_tail)
        elif plan.main:
            carry, y_main = seq_scan(carry, 0, plan.main)
            ys_parts.append(y_main)

        if plan.split_last:
            # last layer: gather outside the checkpointed compute -- its
            # gathered params are saved into backward (first to be needed
            # there), skipping one re-gather, as in FSDP2's skip-reshard-
            # last-block policy; activations still remat
            last_inner = jax.checkpoint(compute, policy=policy)

            def last_body(c, scan_xs):
                layer_bufs, user_xs = scan_xs
                return last_inner(gather_layer(layer_bufs), c, user_xs)

            carry, y_last = lax.scan(last_body, carry, slices(plan.main, n),
                                     length=n - plan.main)
            ys_parts.append(y_last)

        ys_parts = [p for p in ys_parts
                    if p is not None and jax.tree.leaves(p)]
        if not ys_parts:
            ys = None
        elif len(ys_parts) == 1:
            ys = ys_parts[0]
        else:
            ys = jax.tree.map(
                lambda *parts: jnp.concatenate(parts, axis=0), *ys_parts)
        return carry, ys
