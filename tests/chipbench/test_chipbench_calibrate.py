"""``calibrate.py``'s readings on the tiny routed cell, with the
program's expert choices reported (``routing_report.py``): every line
carries the routed numbers, the program's lines are correct, and the
control's, half the batch's, the routing fault's and the drop fault's
are not."""
import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import routing_report  # noqa: E402
import tiny_cell  # noqa: E402
from benchmarks.chip import calibrate, cell  # noqa: E402


@pytest.fixture(scope="module")
def out():
    mp = pytest.MonkeyPatch()
    try:
        tiny_cell.use_routed_reference(mp)
        routing_report.install_in_program(mp, cell.Program)
        wl, conf, traf = tiny_cell.tiny_routed()
        prog = cell.Program(wl, conf, traf, jax.devices())
        return calibrate.calibrate(prog, wl, conf, traf,
                                   list(tiny_cell.SEEDS[:2]), 1)
    finally:
        mp.undo()


def test_lines_carry_the_routed_numbers(out):
    rows = out["program"] + out["control"] + [
        r for rs in out["faults"].values() for r in rs]
    for row in rows:
        assert {"route_gap", "kept_gap", "route_margin",
                "dropped_share"} <= set(row)
    assert set(out["faults"]) == {"half", "route", "drop"}
    for k in calibrate.NUMBERS + calibrate.ROUTE_NUMBERS:
        assert {"lower", "control_min", "half_min", "route_min",
                "drop_min"} == set(out["summary"][k])


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
