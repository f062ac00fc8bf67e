"""The plain reference tied to the program's training step
(``repro.launch.train.build`` + ``FSDPRuntime.make_train_step``) at a
size the CPU runs: the program's first steps read within the tiny
cell's limits, and the control (the reference with fp8 matmul inputs)
does not."""
import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import tiny_cell  # noqa: E402
from benchmarks.chip import cell, check, traffic  # noqa: E402


@pytest.fixture(scope="module")
def readings():
    wl, conf, traf = tiny_cell.tiny()
    devices = jax.devices()
    prog = cell.Program(wl, conf, traf, devices)
    ref = check.Reference(conf, wl["optimizer"])
    ctl = check.Reference(conf, wl["optimizer"], mode="fp8")
    out = []
    for seed in tiny_cell.SEEDS[:2]:
        pool = traffic.batch_pool(traf, conf["vocab_size"], 1, seed)
        params, opt = prog.init_state(seed)
        if prog.compiled is None:
            prog.compile(params, opt, prog.place(pool[0]))
        _, _, got = prog.first_steps(params, opt, pool, seed, 3)
        want = ref.run(seed, pool[:3])
        out.append((got, want, ctl.run(seed, pool[:3])))
    return wl["limits"], out


def test_program_agrees_with_reference(readings):
    limits, runs = readings
    for got, want, _ in runs:
        ok, checks = check.judge(check.gaps(got, want), limits)
        assert ok, checks
        assert got["loss"][0] == pytest.approx(want["loss"][0], rel=1e-2)


def test_control_is_not_correct(readings):
    limits, runs = readings
    for _, want, control in runs:
        ok, checks = check.judge(check.gaps(control, want), limits)
        assert not ok, checks


def test_change_gap_leaves_out_leaves_without_gradient():
    grad = {"a": [1.0, 2.0], "b": 1e-9, "c": 3.0}
    change = {"a": [1.0, 1.0], "b": 5e-3, "c": 1.0}
    got = dict(loss=[1.0], grad=grad, change=dict(change, b=1.0))
    g = check.gaps(got, dict(loss=[1.0], grad=grad, change=change))
    assert g["left_out"] == ["b"] and g["change_gap"] == 0.0
