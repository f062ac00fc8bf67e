"""Compile for a TPU v5e that is described, not attached.

Every Pallas kernel in ``repro.kernels`` and one whole train step of the
chip smoke configuration (Granite-3.0-1B-A400M at published widths, 8
layers, batch 1 x 4096) are compiled by the TPU compiler installed with
libtpu, with ``interpret=False``: Mosaic refuses what interpret mode runs
happily (a 1-D block that is neither the whole array nor a multiple of
128, a tile past the scoped VMEM limit), and XLA refuses a step that does
not fit the chip's memory.  Nothing runs, so these tests say nothing about
values or times -- the interpret-mode parity suites own values.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  Kernel shapes are Granite's shard widths: one layer of
the ``layers`` group (3,180,544 elements on one chip) and one sequence of
4096 tokens; the whole-step test runs the update at all 8 layers.  The
fused causal attention compiles at both benchmark cells' heads and head
sizes (Granite 16 / 8 of 64, Qwen2.5-14B 40 / 8 of 128).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.compat import float8_dtypes

GIB = 1 << 30
BLOCK = 1024
LAYERS = (1, 3_180_544)              # one layer of Granite's `layers` shard
LAYER_BLOCKS = (1, 3_180_544 // BLOCK)
TOKENS = 4096
STORE_FMTS = ["fp32", "bf16", "q8_block"] + sorted(float8_dtypes())


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    """name -> ``lower(sds)``, where ``sds(shape, dtype)`` makes an argument
    and ``lower`` returns the kernel's Lowered."""
    from repro.kernels import (adam8bit_update as a8, adam_update as aw,
                               blockwise_quant as bq, encode_ef as ee,
                               fused_update as fu, q8_matmul as qm)

    f32, bf16, i8 = jnp.float32, jnp.bfloat16, jnp.int8
    L, NB = LAYERS, LAYER_BLOCKS
    flat = (L[0] * L[1],)

    def sc(s):
        return [s((), f32)] * 7

    cases = {
        "quantize": lambda s: bq.quantize.lower(
            s(L, f32), block=BLOCK, interpret=False),
        "dequantize_into": lambda s: bq.dequantize_into.lower(
            s(L, i8), s(NB, f32), block=BLOCK, out_dtype=bf16,
            interpret=False),
        "encode_ef": lambda s: ee.encode_ef.lower(
            s(L, bf16), s(L, f32), block=BLOCK, interpret=False),
        # attention projection (N % block == 0) and expert up-projection
        # (block % N == 0) of Granite, one sequence of activations
        "q8_matmul_d1024": lambda s: qm.q8_matmul.lower(
            s((TOKENS, 1024), bf16), s((1024, 1024), i8),
            s((1024,), f32), block=BLOCK, interpret=False),
        "q8_matmul_ff512": lambda s: qm.q8_matmul.lower(
            s((TOKENS, 1024), bf16), s((1024, 512), i8), s((512,), f32),
            block=BLOCK, interpret=False),
        "adamw_update": lambda s: aw.adamw_update.lower(
            *[s(flat, f32)] * 5, *sc(s), interpret=False),
        "adam8bit_update": lambda s: a8.adam8bit_update.lower(
            s(flat, f32), s(flat, f32), s(flat, i8), s(flat, i8),
            s((flat[0] // BLOCK,), f32), s((flat[0] // BLOCK,), f32),
            s(flat, f32), *sc(s), block=BLOCK, interpret=False),
    }
    for fmt in STORE_FMTS:
        wdt = bf16 if fmt == "bf16" else f32
        cases[f"adamw_store_update_{fmt}"] = (
            lambda s, fmt=fmt, wdt=wdt: fu.adamw_store_update.lower(
                s(L, wdt), s(L, f32), s(L, f32), s(L, f32), s(L, f32),
                *sc(s), fmt=fmt, block=BLOCK, interpret=False))
        cases[f"adam8bit_store_update_{fmt}"] = (
            lambda s, fmt=fmt, wdt=wdt: fu.adam8bit_store_update.lower(
                s(L, wdt), s(L, f32), s(L, i8), s(L, i8), s(NB, f32),
                s(NB, f32), s(L, f32), *sc(s), fmt=fmt, block=BLOCK,
                interpret=False))
    return cases


KERNELS = ["quantize", "dequantize_into", "encode_ef", "q8_matmul_d1024",
           "q8_matmul_ff512", "adamw_update", "adam8bit_update"] + [
    f"{k}_{fmt}" for k in ("adamw_store_update", "adam8bit_store_update")
    for fmt in STORE_FMTS]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip):
    lowered = _kernel_cases()[name](
        lambda shape, dt: _sds(one_chip, shape, dt))
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


ATTENTION_KERNELS = ("flash_attention_fwd", "flash_attention_dq",
                     "flash_attention_dkv")
#: the cells' attention: query heads, KV heads, head size, score multiplier
ATTENTION = {"granite": (16, 8, 64, 1 / 64),
             "qwen": (40, 8, 128, 128 ** -0.5)}


def _kernel_calls(text: str, kernel: str) -> list[str]:
    """The compiled program's lines that define a call of ``kernel``."""
    return [line for line in text.splitlines()
            if re.match(rf"\s*(ROOT )?%{kernel}(\.\d+)? = ", line)]


@pytest.mark.parametrize("cell", sorted(ATTENTION))
def test_flash_attention_compiles_for_v5e(cell, one_chip):
    """Forward and backward of the fused causal attention at a cell's
    heads and head size, one sequence of 4096 tokens: three Mosaic
    kernels."""
    from repro.kernels.flash_attention import causal_attention

    hq, hkv, hd, scale = ATTENTION[cell]

    def sds(heads):
        return _sds(one_chip, (1, heads, TOKENS, hd), jnp.bfloat16)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: causal_attention(
            *a, scale=scale, interpret=False), q, k, v)
        return out, vjp(do)

    text = jax.jit(fwd_bwd).lower(sds(hq), sds(hkv), sds(hkv),
                                  sds(hq)).compile().as_text()
    for kernel in ATTENTION_KERNELS:
        calls = _kernel_calls(text, kernel)
        assert calls and all("tpu_custom_call" in c for c in calls), kernel


def test_smoke_train_step_compiles_for_v5e(topo, monkeypatch):
    """The whole chip-smoke step at full width: Mosaic kernels inside, the
    fused attention's among them under the ``model.attn`` scope, and
    arguments plus temporaries within one chip's 16 GiB."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import build

    # ops dispatches on jax.default_backend(), which is the CPU here
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m"),
                              n_layers=8)
    mesh = make_local_mesh(1, 1, devices=topo.devices[:1])
    runtime, optimizer = build(cfg, mesh)
    tokens = jax.ShapeDtypeStruct((1, TOKENS), jnp.int32)
    batch = {"tokens": jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype,
        sharding=NamedSharding(mesh, runtime.batch_pspec(
            {"tokens": tokens})["tokens"]))}
    step = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    compiled = runtime.make_train_step(optimizer).lower(
        runtime.param_shapes(), optimizer.state_shapes(runtime), step,
        batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for kernel in ATTENTION_KERNELS:
        calls = _kernel_calls(text, kernel)
        assert calls, kernel
        assert all("tpu_custom_call" in c and "model.attn" in c
                   for c in calls), kernel
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= 16 * GIB, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes)


def _estimated_cycles(hlo_text: str) -> int:
    """Sum of the TPU cost model's ``estimated_cycles`` over the compiled
    program's instructions (each one's ``backend_config``)."""
    return sum(int(c) for c in
               re.findall(r'"estimated_cycles":"(\d+)"', hlo_text))


def test_wd_mask_costs_about_a_write_for_v5e(topo):
    """The weight-decay mask of one Qwen2.5-14B layer shard (1 x 275,268,608
    elements on one chip, q/k/v, o and the MLP as three runs of matrices)
    costs the cost model at most 3 times a plain fill of the same elements.

    The fill is ``jnp.ones`` over the dense (S/128, 128) view: a (1, S) fill
    lands in the sparse T(1,128) tiling, which the cost model puts above the
    per-matrix mask this construction replaced (50.7M against 36.1M cycles),
    so it could not tell the two apart."""
    from repro.compat import shard_map
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import build
    from repro.optim.common import matrix_mask_local

    mesh = make_local_mesh(1, 1, devices=topo.devices[:1])
    runtime, _ = build(dataclasses.replace(get_config("qwen2.5-14b"),
                                           n_layers=1), mesh)
    lo = runtime.layouts["layers"]
    S = lo.plan.shard_size
    assert S == 275_268_608

    def compiled_text(f, spec):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=(),
                                 out_specs=spec)).lower().compile().as_text()

    mask = _estimated_cycles(compiled_text(
        lambda: matrix_mask_local(runtime, lo, (1, S)), P(None, "data")))
    fill = _estimated_cycles(compiled_text(
        lambda: jnp.ones((S // 128, 128), jnp.float32), P("data")))
    assert 0 < mask <= 3 * fill, (mask, fill)
