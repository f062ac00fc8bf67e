#!/usr/bin/env python3
"""Chip smoke test: the FSDP trainer's main path on a TPU, end to end.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # 4-way ZeRO-3 vs the same batch on one chip

One chip (the default) trains Granite-3.0-1B-A400M at its published widths,
cut to 8 of its 24 layers, through ``repro.launch.train.build`` and
``train_loop`` -- the functions ``repro.launch.train`` runs -- with the fp32
store, AdamW, a 1x1 mesh and one repeated seeded batch of 1 x 4096 tokens.
It checks that the compiled step holds a Mosaic kernel (``tpu_custom_call``:
the fused AdamW update, not its interpreter), that loss and grad norm stay
finite and the loss falls, and that ``ops.adamw_store_update`` on the
device matches ``kernels/ref.py`` within its PARITY class on one real
group shard.

``--chips 4`` runs only the phase that exists only across chips: the same
model on a ``data=4`` mesh at global batch 4 x 4096 (parameter all-gather,
gradient reduce-scatter), compared step by step with that global batch on
one chip as 4 microbatches.

Every check prints a line and raises on failure; nothing falls back to the
CPU.  The last line of stdout is one JSON object naming the device.
Step wall times printed here are smoke timings, not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "granite-moe-1b-a400m"
N_LAYERS = 8          # the only cut: one of three 8-layer pipeline stages
SEQ = 4096
SEED = 0
STEPS = 6             # >= 5 finite steps on one repeated batch
STEPS_4CHIP = 3
# 4-chip vs 1-chip agreement.  Both runs compute every sample's forward
# and backward in bf16 at the same per-sample shape; they differ in how
# gradients are summed: a bf16 collective over 4 chips against fp32
# accumulation over 4 microbatches.  bf16 keeps 8 significand bits
# (2**-8 ~ 3.9e-3 relative per rounding), so loss and grad norm may move
# by a few bf16 roundings: rtol = 1e-2 (~2.5 roundings).
RTOL_4CHIP = 1e-2


def check(ok: bool, what: str) -> None:
    print(f"check {what}: {'pass' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def smoke_config():
    """Granite-3.0-1B-A400M at published widths, 8 of its 24 layers."""
    from repro.configs import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS)
    print(f"config {cfg.name} {cfg.source}: d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.hd} "
          f"experts={cfg.n_experts} top_k={cfg.top_k} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab}", flush=True)
    print(f"cut: n_layers {full.n_layers} -> {cfg.n_layers} (widths "
          f"unchanged); stands for the {full.n_layers}-layer model as "
          f"{full.n_layers // N_LAYERS} pipeline stages of {N_LAYERS} "
          f"layers, each stage whole on one chip", flush=True)
    return cfg


def _scalar(mesh, value: int = 0):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(jnp.int32(value), NamedSharding(mesh, P()))


def _compile_step(runtime, optimizer, params, opt_state, batch):
    """(lowered, compiled, compile seconds) of the runtime's train step."""
    step_fn = runtime.make_train_step(optimizer)
    t0 = time.perf_counter()
    lowered = step_fn.lower(params, opt_state, _scalar(runtime.mesh), batch)
    compiled = lowered.compile()
    return lowered, compiled, time.perf_counter() - t0


def _train(runtime, compiled, params, opt_state, batch, steps, tag):
    """Run ``steps`` steps of one repeated batch through train_loop;
    returns (params, opt_state, [(loss, grad_norm)])."""
    from repro.launch.train import train_loop

    hist = []
    t = [time.perf_counter()]

    def on_step(i, params, opt_state, metrics):
        loss = float(metrics["loss"])  # waits for the step
        gnorm = float(metrics["grad_norm"])
        now = time.perf_counter()
        hist.append((loss, gnorm))
        print(f"{tag} step {i} loss {loss!r} grad_norm {gnorm!r} "
              f"wall_s {now - t[0]!r} (smoke timing, not a measurement)",
              flush=True)
        t[0] = now

    params, opt_state = train_loop(runtime, compiled, params, opt_state,
                                   lambda i: batch, steps, on_step=on_step)
    return params, opt_state, hist


def _ulp_distance(a, b) -> tuple[int, int]:
    """(max integer-view distance, differing elements) of two arrays."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    view = {1: np.int8, 2: np.int16, 4: np.int32}[a.dtype.itemsize]
    d = np.abs(a.view(view).astype(np.int64) - b.view(view).astype(np.int64))
    return int(d.max(initial=0)), int((d > 0).sum())


def phase_train(cfg, devices, *, seq: int = SEQ, steps: int = STEPS):
    """Train the smoke config on one device; returns the final
    (runtime, params, opt_state)."""
    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import build, n_params

    mesh = make_local_mesh(1, 1, devices=devices[:1])
    runtime, optimizer = build(cfg, mesh)
    stores = sorted({lo.store.fmt for lo in runtime.layouts.values()})
    print(f"train: params {n_params(runtime)} store {stores} optimizer "
          f"{cfg.optimizer} mesh 1x1 batch 1x{seq} steps {steps}",
          flush=True)
    params = runtime.init_params(SEED)
    opt_state = optimizer.init(runtime)
    stream = SyntheticStream(DataConfig(cfg.vocab, seq, 1, seed=SEED), cfg)
    batch = stream.shard(stream.batch(0), runtime)

    _, compiled, compile_s = _compile_step(runtime, optimizer, params,
                                           opt_state, batch)
    hlo = compiled.as_text()
    n_kernels = hlo.count("custom_call_target=\"tpu_custom_call\"")
    print(f"train: compile_s {compile_s!r} tpu_custom_calls {n_kernels}",
          flush=True)
    check(n_kernels > 0, "compiled step holds a Mosaic kernel "
                         "(tpu_custom_call)")

    params, opt_state, hist = _train(runtime, compiled, params, opt_state,
                                     batch, steps, "train")
    check(len(hist) >= 5 and all(math.isfinite(l) and math.isfinite(g)
                                 for l, g in hist),
          f"loss and grad norm finite at all {len(hist)} steps")
    check(hist[-1][0] < hist[0][0],
          f"last loss {hist[-1][0]!r} below first {hist[0][0]!r}")
    stats = devices[0].memory_stats() or {}
    print(f"train: peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
          flush=True)
    return runtime, params, opt_state


def phase_kernel_parity(runtime, params, opt_state, group: str = "layers"):
    """ops.adamw_store_update on the device vs kernels/ref.py on one real
    group shard (trained weights and moments, a seeded gradient)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    store = runtime.layouts[group].store
    w = params[group]
    m, v = opt_state["m"][group], opt_state["v"][group]
    kg, km = jax.random.split(jax.random.key(SEED))
    g = jax.random.normal(kg, w.shape, jnp.float32) * 1e-3
    mask = jax.random.bernoulli(km, 0.5, w.shape).astype(jnp.float32)
    sc = tuple(jnp.float32(x) for x in (3e-4, 0.9, 0.95, 1e-8, 0.1,
                                        1 - 0.9 ** 7, 1 - 0.95 ** 7))
    got = ops.adamw_store_update(
        w, g, m, v, mask, lr=sc[0], b1=sc[1], b2=sc[2], eps=sc[3],
        wd=sc[4], c1=sc[5], c2=sc[6], fmt=store.fmt, block=store.block)
    want = jax.jit(ref.adamw_store_update_ref, static_argnums=(12, 13))(
        w, g, m, v, mask, *sc, store.fmt, store.block)
    (w2, m2, v2), (w_ref, m_ref, v_ref) = got, want
    n_diff = 0
    for name, a, b in (("w", w2, w_ref), ("m", m2, m_ref), ("v", v2, v_ref)):
        ulp, n = _ulp_distance(a, b)
        n_diff += n
        print(f"parity adamw_store_update[{store.fmt}] {group} "
              f"{tuple(w.shape)} {name}: differing {n}/{a.size} "
              f"max_int_distance {ulp} max_abs_diff "
              f"{float(jnp.max(jnp.abs(a - b)))!r}", flush=True)
    check(n_diff == 0, "on-device adamw_store_update within its PARITY "
                       "class (BITWISE vs kernels/ref.py)")


def _collectives(hlo: str) -> dict:
    """{collective: (calls, largest result in elements)} of compiled HLO
    text (async ``-start`` halves counted under the collective's name)."""
    out = {}
    pat = re.compile(r"= (\(?[^=]*?\)?) (all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start)?\(")
    for line in hlo.splitlines():
        mt = pat.search(line)
        if not mt:
            continue
        elems = max((math.prod(int(d) for d in dims.split(",") if d)
                     for dims in re.findall(r"\[([0-9,]*)\]", mt.group(1))),
                    default=0)
        calls, big = out.get(mt.group(2), (0, 0))
        out[mt.group(2)] = (calls + 1, max(big, elems))
    return out


def _bytes_in_use(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices]


def _peaks(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def phase_fsdp4(cfg, devices, *, seq: int = SEQ, steps: int = STEPS_4CHIP):
    """4-way ZeRO-3 on a data=4 mesh vs the same global batch on one
    device as 4 microbatches."""
    import jax

    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import build

    check(len(devices) == 4, f"four devices ({len(devices)} found)")
    stream = SyntheticStream(DataConfig(cfg.vocab, seq, 4, seed=SEED), cfg)
    host_batch = stream.batch(0)

    # --- 4-way FSDP ------------------------------------------------------
    mesh4 = make_local_mesh(4, 1, devices=devices)
    rt4, opt4 = build(cfg, mesh4)
    params = rt4.init_params(SEED)
    opt_state = opt4.init(rt4)
    leaves = jax.tree.leaves((params, opt_state))
    total = sum(x.nbytes for x in leaves)
    held = [sum(sh.data.nbytes for x in leaves for sh in x.addressable_shards
                if sh.device == d) for d in devices]
    print(f"fsdp4: state bytes held per device {held} (all state {total}); "
          f"bytes_in_use {_bytes_in_use(devices)}", flush=True)
    check(all(abs(h - total / 4) <= 0.01 * total for h in held),
          "params and optimizer state sharded 4 ways")
    batch = stream.shard(host_batch, rt4)
    lowered, compiled, compile_s = _compile_step(rt4, opt4, params,
                                                 opt_state, batch)
    hlo = compiled.as_text()
    coll = _collectives(hlo)
    print(f"fsdp4: compile_s {compile_s!r} collectives (calls, largest "
          f"elements) {coll}", flush=True)
    check("all-gather" in coll, "compiled 4-chip step all-gathers "
                                "parameters")
    # the program asks for a reduce-scatter (psum_scatter in the gather's
    # transpose).  XLA:TPU may emit it as a reduce-scatter, or as an
    # all-reduce of the whole gradient that each chip then slices; the
    # line above shows which, and the step must hold one of the two
    check("reduce_scatter" in lowered.as_text(),
          "lowered 4-chip step reduce-scatters gradients")
    layer = rt4.layouts["layers"].plan.shard_size
    check("reduce-scatter" in coll
          or coll.get("all-reduce", (0, 0))[1] >= 4 * layer,
          "compiled 4-chip step reduces gradients across chips (as "
          "reduce-scatter, or as a gradient-sized all-reduce)")
    params, opt_state, hist4 = _train(rt4, compiled, params, opt_state,
                                      batch, steps, "fsdp4")
    peaks4 = _peaks(devices)
    print(f"fsdp4: peak_bytes_in_use per device {peaks4}", flush=True)
    for leaf in jax.tree.leaves((params, opt_state, batch)):
        leaf.delete()
    del compiled, lowered

    # --- same global batch, one device, 4 microbatches ---------------------
    cfg1 = dataclasses.replace(cfg, parallel=dataclasses.replace(
        cfg.parallel, microbatches=4))
    mesh1 = make_local_mesh(1, 1, devices=devices[:1])
    rt1, opt1 = build(cfg1, mesh1)
    params = rt1.init_params(SEED)
    opt_state = opt1.init(rt1)
    batch = stream.shard(host_batch, rt1)
    _, compiled, compile_s = _compile_step(rt1, opt1, params, opt_state,
                                           batch)
    print(f"micro4: compile_s {compile_s!r}", flush=True)
    params, opt_state, hist1 = _train(rt1, compiled, params, opt_state,
                                      batch, steps, "micro4")
    peak1 = _peaks(devices)[0]
    print(f"micro4: peak_bytes_in_use device 0 {peak1}", flush=True)

    check(max(peaks4) <= 1.25 * min(peaks4),
          "4-way peaks balanced across devices (max <= 1.25 x min)")
    check(max(peaks4) < peak1,
          "each 4-way device peaks below the one-chip run")
    for i, ((l4, g4), (l1, g1)) in enumerate(zip(hist4, hist1)):
        rl = abs(l4 - l1) / abs(l1)
        rg = abs(g4 - g1) / abs(g1)
        print(f"compare step {i}: loss rel diff {rl!r} grad_norm rel diff "
              f"{rg!r}", flush=True)
        check(rl <= RTOL_4CHIP and rg <= RTOL_4CHIP,
              f"step {i} 4-way FSDP agrees with 1-chip x4 microbatches "
              f"(rtol {RTOL_4CHIP})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + kernel parity on one chip; 4: only "
                         "4-way FSDP vs the 1-chip microbatched run")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.train import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    print(f"device platform {dev.platform} device_kind {dev.device_kind} "
          f"count {len(devices)}", flush=True)
    print(f"compile cache {enable_compile_cache()}", flush=True)
    cfg = smoke_config()
    if args.chips == 4:
        phase_fsdp4(cfg, devices[:4])
        count = 4
    else:
        runtime, params, opt_state = phase_train(cfg, devices)
        phase_kernel_parity(runtime, params, opt_state)
        count = len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
