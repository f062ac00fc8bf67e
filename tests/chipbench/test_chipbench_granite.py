"""The Granite cell cut to the CPU: the published Granite-3.0-1B-A400M
(registry ``granite-3.0-1b-a400m``: four multipliers, tied head, dropless
top-k routing by grouped matmul) at 2 layers, 8 experts, top-2, hidden
128, judged through ``cell.Program`` against ``references/granitemoe.py``
under the routing that the step itself reports.  The program is
``correct``; the fp8 control, the routing fault and the drop fault are
not; and the grouped-matmul layer's groups add up to the layer."""
import copy
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import calibrate, cell, check, traffic  # noqa: E402

CELL = "granite-l8.train.b1s4096"
SIZES = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=64, vocab_size=512, num_hidden_layers=2,
             num_local_experts=8, num_experts_per_tok=2)
PROGRAM = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
               head_dim=32, d_ff=64, vocab=512, n_experts=8, top_k=2)
SEEDS = (11, 1011)
# CPU readings at this size, the step's own routing handed over (seeds
# 11, 1011, 2011, 3011): the program reads at most 3.0e-5 / 6.7e-4 /
# 1.3e-3 (loss / median-leaf gradient / worst-leaf change), a reference
# with bfloat16 matmuls alike, and its largest route margin is 0.036
# logits; the fp8 control, routing itself, at least 2.0e-4 / 3.6e-3 /
# 6.4e-3; the routing fault a route_gap of 0.58 or more; the drop fault a
# kept_gap of 0.066 or more.  kept_gap is exact: a dropless program keeps
# every choice.  These limits hold for this size and are no chip cell's.
LIMITS = {"loss_gap": 9e-5, "grad_median_gap": 1.5e-3, "change_gap": 3e-3,
          "route_gap": 0.0, "kept_gap": 0.0}


def tiny():
    """(workload, configuration, traffic) dicts of the tiny Granite
    cell: 256 tokens a step."""
    wl, conf, traf = cell.load(CELL)
    conf = copy.deepcopy(conf)
    conf.update(SIZES)
    conf["assumed"]["head_dim"] = 32
    conf["program"]["replace"] = dict(PROGRAM)
    traf = dict(traf, seq=256, pool=4)
    wl = dict(wl, limits=dict(LIMITS), trace_seconds=1)
    return wl, conf, traf


@pytest.fixture(scope="module")
def out():
    wl, conf, traf = tiny()
    prog = cell.Program(wl, conf, traf, jax.devices())
    return calibrate.calibrate(prog, wl, conf, traf, list(SEEDS), 1)


def test_program_correct_control_and_faults_not(out):
    c = out["summary"]["correct"]
    assert c["program"] == [True, True]
    assert c["control"] == [False]
    assert c["half"] == [False] and c["route"] == [False]
    assert c["drop"] == [False]
    assert out["summary"]["route_gap"]["lower"] == 0.0
    assert out["summary"]["route_gap"]["route_min"] > 0.25
    assert out["summary"]["kept_gap"]["lower"] == 0.0
    assert out["summary"]["kept_gap"]["drop_min"] > 0
    assert out["summary"]["dropped_share"]["lower"] == 0.0


def test_control_sits_three_times_above_the_program(out):
    s = out["summary"]
    assert any(s[k]["control_min"] >= 3 * s[k]["lower"]
               for k in ("loss_gap", "grad_median_gap", "change_gap"))


def test_whole_run_with_the_steps_own_routing_is_correct():
    wl, conf, traf = tiny()
    r = cell.run("tiny-granite", SEEDS[0], 0.5, False,
                 t_start=time.perf_counter(), require_tpu=False, wl=wl,
                 conf=conf, traf=traf)
    assert r["correct"], r["checks"]
    assert r["checks"]["kept_gap"]["value"] == 0.0
    assert list(r)[-1] == "checks"


def test_grouped_layer_adds_up_to_the_layer():
    """The program's routed MLP (grouped matmuls over uneven groups)
    against the reference's, expert by expert: each expert's part, the
    reference with every other expert's gate set to nought, summed over
    the experts, is the whole layer."""
    import dataclasses

    from benchmarks.chip.references import granitemoe
    from repro.configs import get_config
    from repro.models.moe import moe_ffn

    _, conf, _ = tiny()
    m = granitemoe.Model(conf)
    cfg = dataclasses.replace(get_config("granite-3.0-1b-a400m"), **PROGRAM)
    rng = np.random.default_rng(3)
    D, F, E = m.D, m.F, m.E
    p = {"moe_router": rng.standard_normal((D, E)) / np.sqrt(D),
         "moe_w1": rng.standard_normal((E, D, F)) / np.sqrt(D),
         "moe_w3": rng.standard_normal((E, D, F)) / np.sqrt(D),
         "moe_w2": rng.standard_normal((E, F, D)) / np.sqrt(F)}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    h = jnp.asarray(rng.standard_normal((2, 64, D)), jnp.float32)
    got, _, routing = moe_ffn(cfg, p, h)
    sizes = np.bincount(np.asarray(routing["experts"]).ravel(), minlength=E)
    assert sizes.sum() == 2 * 64 * m.k and len(set(sizes.tolist())) > 1
    parts = []
    for e in range(E):
        one = dict(p, moe_w2=p["moe_w2"] * (jnp.arange(E) == e)[:, None,
                                                              None])
        part, _, _ = granitemoe._moe(m, one, h, routing, "given", 1, "fp32")
        parts.append(part)
    whole, _, _ = granitemoe._moe(m, p, h, routing, "given", 1, "fp32")
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_granite_pool_and_reference_shapes():
    wl, conf, traf = tiny()
    pool = traffic.batch_pool(traf, conf["vocab_size"], 1, 5)
    assert pool.shape == (4, 1, 256)
    ref = check.Reference(conf, wl["optimizer"])
    assert ref.routed and "head" not in ref.shapes
