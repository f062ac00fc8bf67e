"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
        --steps 50 --batch 8 --seq 128

On a CPU host, --reduced (smoke-scale) is the realistic mode; the full
configs are exercised by the dry run and, cut to one chip's share, by
``chip_smoke.py`` on a TPU.  ``main`` wires data pipeline, FSDP runtime,
optimizer, metrics, and periodic checkpointing.

``build`` and ``train_loop`` are the training main path: ``main`` and
``chip_smoke.py`` both run through them.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Callable, Optional

#: the checkout this module runs from (src/repro/launch/train.py -> root)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside
    and JAX reads it itself, so nothing is set here.  Otherwise the cache
    lives at the fixed path ``<checkout>/.jax_cache``, never one built from
    a temp name, a pid or the time, so the next run in the same checkout
    finds what this one compiled."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build(cfg, mesh, *, planner: str = "ragged", policies=None,
          cost_model=None):
    """(runtime, optimizer) for ``cfg`` on ``mesh``: the model, its
    ShardingPlan resolved by ``FSDPRuntime``, and the config's optimizer."""
    from ..configs import build_model
    from ..core.fsdp import FSDPRuntime
    from ..optim import make_optimizer

    runtime = FSDPRuntime(build_model(cfg), mesh, planner=planner,
                          policies=policies, cost_model=cost_model)
    return runtime, make_optimizer(cfg)


def n_params(runtime) -> int:
    """Parameters the plan holds, over every group and layer."""
    return sum(int(lo.plan.payload) * (lo.n_layers or 1) * lo.outer_size
               for lo in runtime.layouts.values())


def train_loop(runtime, step_fn: Callable, params, opt_state,
               batch_for: Callable, steps: int, *, start: int = 0,
               on_step: Optional[Callable] = None):
    """Run ``step_fn`` (``runtime.make_train_step(optimizer)`` or its
    compiled executable) for steps ``start .. steps-1`` on ``batch_for(i)``.
    ``on_step(i, params, opt_state, metrics)`` sees every step's result.
    Returns the final ``(params, opt_state)``.

    Each iteration is a ``train.step`` span holding ``train.batch`` and
    ``train.dispatch`` (``repro.spans``), seen by any ``jax.profiler``
    trace taken around the loop."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import spans

    step = jax.device_put(jnp.int32(start), NamedSharding(runtime.mesh, P()))
    for i in range(start, steps):
        with StepTraceAnnotation(spans.TRAIN_STEP, step_num=i):
            with TraceAnnotation(spans.TRAIN_BATCH):
                batch = batch_for(i)
            with TraceAnnotation(spans.TRAIN_DISPATCH):
                params, opt_state, step, metrics = step_fn(
                    params, opt_state, step, batch)
            if on_step is not None:
                on_step(i, params, opt_state, metrics)
    return params, opt_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--data", type=int, default=1, help="data axis size")
    ap.add_argument("--model", type=int, default=1, help="model axis size")
    ap.add_argument("--planner", default="ragged")
    ap.add_argument("--policies", default=None,
                    help="sharding policies: 'auto' runs the structure-"
                         "aware cost model per group (core.policy); default "
                         "lowers the config's legacy knobs")
    ap.add_argument("--profile", default=None,
                    help="measured comm profile JSON (BENCH_comm.json from "
                         "benchmarks.bench_comm): '--policies auto' prices "
                         "formats and ring chunking from the calibrated "
                         "curves instead of the builtin roofline")
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore params/opt state from --ckpt if it exists "
                         "(any saved plan/mesh/TP degree: cross-plan loads "
                         "stream through the extent map) and continue from "
                         "the saved step")
    ap.add_argument("--tp", type=int, default=0,
                    help="override the arch config's tensor-parallel degree "
                         "(requires --model >= the degree)")
    ap.add_argument("--verify", action="store_true",
                    help="statically verify the plan's declared comm/memory/"
                         "dtype invariants against the traced step "
                         "(repro.analysis) before running; abort on any "
                         "violation")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    from ..checkpoint import ckpt
    from ..configs import get_config
    from ..data.pipeline import DataConfig, SyntheticStream
    from .mesh import make_local_mesh

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.optimizer:
        cfg = dataclasses.replace(cfg, optimizer=args.optimizer)
    if args.tp:
        par = cfg.parallel
        if args.tp > 1:
            par = dataclasses.replace(
                par, tp=args.tp,
                fsdp_axes=tuple(a for a in par.fsdp_axes if a != "model")
                or ("data",))
        else:
            par = dataclasses.replace(par, tp=1)
        cfg = dataclasses.replace(cfg, parallel=par)
    mesh = make_local_mesh(args.data, args.model)
    cost_model = None
    if args.profile:
        from ..core.policy import CostModel

        cost_model = CostModel.from_profile(args.profile)
    runtime, optimizer = build(cfg, mesh, planner=args.planner,
                               policies=args.policies, cost_model=cost_model)
    print(runtime.plan.describe())
    if args.verify:
        from ..analysis import verify_runtime

        report = verify_runtime(runtime, optimizer,
                                profile_path=args.profile)
        print(report.summary())
        report.raise_if_failed()

    params = runtime.init_params(args.seed)
    opt_state = optimizer.init(runtime)
    start = 0
    if args.resume and args.ckpt:
        if (pathlib.Path(args.ckpt) / "meta.json").exists():
            params, start, opt_state = ckpt.load(args.ckpt, runtime,
                                                 opt_state)
            print(f"resumed {args.ckpt} @ step {start}")
    step_fn = runtime.make_train_step(optimizer)
    stream = SyntheticStream(
        DataConfig(cfg.vocab, args.seq, args.batch, seed=args.seed), cfg)

    print(f"arch={cfg.name} params={n_params(runtime)/1e6:.1f}M "
          f"planner={args.planner} optimizer={cfg.optimizer} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    t0 = time.time()

    def on_step(i, params, opt_state, metrics):
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            tok_s = (i + 1) * args.batch * args.seq / max(dt, 1e-9)
            print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"tok/s {tok_s:,.0f}")
        if args.ckpt and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt, runtime, params, opt_state, step=i + 1)
            print(f"checkpoint @ step {i+1} -> {args.ckpt}")

    params, opt_state = train_loop(
        runtime, step_fn, params, opt_state,
        lambda i: stream.shard(stream.batch(i), runtime), args.steps,
        start=start, on_step=on_step)
    if args.ckpt:
        ckpt.save(args.ckpt, runtime, params, opt_state, step=args.steps)
        print(f"final checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
