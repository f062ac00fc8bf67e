"""The harness judges a routed cell under the routing that the program
applied: the tiny routed cell (the program's granite-moe-1b-a400m cut to
the CPU) against its routed reference, with the program's expert choices
handed over step by step (``routing_report.py`` stands in for the step's
own report).  The program reads within the tiny limits and its
``route_gap`` and ``kept_gap`` are 0; the fp8 control, the routing fault
and the drop fault are not correct; ``dropped_share`` counts the dropped
choices; the routing of two microbatches is judged in the batch's token
order; and a routed reference refuses to run without the program's
routing, as a routed cell refuses limits that leave the routed numbers
out."""
import pathlib
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import routing_report  # noqa: E402
import tiny_cell  # noqa: E402
from benchmarks.chip import cell, check, traffic  # noqa: E402

NUMBERS = ("loss_gap", "grad_gap", "change_gap")


@pytest.fixture(scope="module", autouse=True)
def _routed_reference():
    mp = pytest.MonkeyPatch()
    tiny_cell.use_routed_reference(mp)
    yield
    mp.undo()


def _first_steps(wl, conf, traf, seeds):
    """[(seed, pool, program readings)] of the program with its routing
    reported."""
    mp = pytest.MonkeyPatch()
    try:
        prog = cell.Program(wl, conf, traf, jax.devices())
        report = routing_report.RoutingReport(prog.cfg).install(mp)
        out = []
        for seed in seeds:
            pool = traffic.batch_pool(traf, conf["vocab_size"], 1, seed)
            params, opt = prog.init_state(seed)
            if prog.compiled is None:
                prog.compile(params, opt, prog.place(pool[0]))
                prog.compiled = report.wrap(prog.compiled)
            _, _, got = prog.first_steps(params, opt, pool, seed, 3)
            out.append((seed, pool, got))
        assert report.recomputed_differs == 0
        return out
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def readings():
    """Per seed: the program's gaps, the control's, the routing fault's
    and the drop fault's, each against the reference handed the routing
    that side applied."""
    wl, conf, traf = tiny_cell.tiny_routed()
    ref = check.Reference(conf, wl["optimizer"])
    ctl = check.Reference(conf, wl["optimizer"], mode="fp8")
    out = []
    for seed, pool, got in _first_steps(wl, conf, traf,
                                        tiny_cell.SEEDS[:2]):
        want = ref.run(seed, pool[:3], routing=got["routing"])
        control = ctl.run(seed, pool[:3], routing="own")
        fault = ref.run(seed, pool[:3], fault="route",
                        routing=got["routing"])
        drop = ref.run(seed, pool[:3], fault="drop", routing=got["routing"])
        out.append({
            "got": got,
            "program": check.gaps(got, want),
            "control": check.gaps(control, ref.run(
                seed, pool[:3], routing=control["routing"])),
            "fault": check.gaps(fault, ref.run(
                seed, pool[:3], routing=fault["routing"])),
            "drop": check.gaps(drop, ref.run(
                seed, pool[:3], routing=drop["routing"]))})
    return wl["limits"], out


def test_program_reads_within_the_tiny_routed_limits(readings):
    limits, runs = readings
    for r in runs:
        ok, checks = check.judge(r["program"], limits)
        assert ok, checks
        assert r["program"]["route_gap"] == 0.0
        assert r["program"]["kept_gap"] == 0.0
        assert r["program"]["route_margin"] < check.ROUTE_TIE


def test_control_and_routing_fault_are_not_correct(readings):
    limits, runs = readings
    for r in runs:
        ok, checks = check.judge(r["control"], limits)
        assert not ok, checks
        ok, checks = check.judge(r["fault"], limits)
        assert not ok, checks
        assert checks["route_gap"]["value"] > 0.25


def test_drop_fault_is_not_correct(readings):
    """The program's experts with its capacity counted in reverse token
    order: the reference applies the drops it is given, and ``kept_gap``
    sees that they are not its model's."""
    limits, runs = readings
    for r in runs:
        assert r["program"]["dropped_share"] > 0
        ok, checks = check.judge(r["drop"], limits)
        assert not ok, checks
        assert checks["kept_gap"]["value"] > 0
        assert r["drop"]["dropped_share"] == r["program"]["dropped_share"]


def test_control_sits_three_times_above_the_program(readings):
    """On at least one of the three gaps, the control's lowest reading
    is three times the program's highest."""
    _, runs = readings
    assert any(min(r["control"][k] for r in runs)
               >= 3 * max(r["program"][k] for r in runs) for k in NUMBERS)


def test_dropped_share_counts_dropped_choices(readings):
    _, runs = readings
    for r in runs:
        kept = np.concatenate([s["kept"].ravel()
                               for s in r["got"]["routing"]])
        assert r["program"]["dropped_share"] == (~kept).sum() / kept.size
    assert any(r["program"]["dropped_share"] > 0 for r in runs)


def test_route_numbers_of_a_hand_made_routing():
    experts = np.array([[[0, 1], [2, 3]]], np.int32)
    kept = np.array([[[True, True], [True, False]]])
    margin = np.array([[[0.0, 2 * check.ROUTE_TIE], [0.5 * check.ROUTE_TIE,
                                                     9.0]]], np.float32)
    capacity = np.array([[[True, True], [False, False]]])
    got = [{"experts": experts, "kept": kept}]
    ref = {"routing": got, "margin": [margin], "capacity": [capacity]}
    g = check.route_numbers(got, ref)
    # the dropped choice's margin does not count; the reference's model
    # drops the third choice, which the program kept
    assert g == {"route_gap": 1 / 3, "kept_gap": 0.25,
                 "route_margin": 2 * check.ROUTE_TIE, "dropped_share": 0.25}
    other = [{"experts": experts[..., ::-1], "kept": kept}]
    with pytest.raises(ValueError, match="another routing"):
        check.route_numbers(got, dict(ref, routing=other))
    # a step that keeps nothing fails route_gap whatever the margins
    none = [{"experts": experts, "kept": np.zeros_like(kept)}]
    g = check.route_numbers(none, dict(ref, routing=none))
    assert g["route_gap"] == 1.0 and g["kept_gap"] == 0.5


def test_routing_of_two_microbatches_is_in_batch_order():
    """Two rows a step in two microbatches: the reported routing reads
    within the limits, and the same routing with the microbatches
    swapped does not."""
    wl, conf, traf = tiny_cell.tiny_routed(microbatches=2, rows=2)
    ref = check.Reference(conf, wl["optimizer"], groups=cell.token_groups(wl))
    [(seed, pool, got)] = _first_steps(wl, conf, traf, tiny_cell.SEEDS[:1])
    n = pool.shape[2]
    assert got["routing"][0]["experts"].shape == (2, 2 * n, 2)
    ok, checks = check.judge(
        check.gaps(got, ref.run(seed, pool[:3], routing=got["routing"])),
        wl["limits"])
    assert ok, checks
    swapped = [{k: np.concatenate([v[:, n:], v[:, :n]], axis=1)
                for k, v in r.items()} for r in got["routing"]]
    bad = dict(got, routing=swapped)
    ok, checks = check.judge(
        check.gaps(bad, ref.run(seed, pool[:3], routing=swapped)),
        wl["limits"])
    assert not ok, checks


def test_a_routed_reference_needs_the_routing():
    wl, conf, traf = tiny_cell.tiny_routed()
    ref = check.Reference(conf, wl["optimizer"])
    pool = traffic.batch_pool(traf, conf["vocab_size"], 1, 5)
    with pytest.raises(ValueError, match="reported none"):
        ref.run(5, pool[:3])
    short = [{"experts": np.zeros((2, 7, 2), np.int32),
              "kept": np.ones((2, 7, 2), bool)}] * 3
    with pytest.raises(ValueError, match="the batch needs"):
        ref.run(5, pool[:3], routing=short)
    dwl, dconf, _ = tiny_cell.tiny()
    with pytest.raises(ValueError, match="without experts"):
        check.Reference(dconf, dwl["optimizer"]).run(5, pool[:3],
                                                     routing=short)


def test_a_routed_cell_needs_the_routing_and_its_numbers():
    """Checked before the window opens: a routed cell whose step reported
    no routing, or whose limits leave out a routed number, is an error;
    a dense cell is not held to either."""
    wl, conf, _ = tiny_cell.tiny_routed()
    routing = {"routing": []}
    check.require_routing(conf, wl["limits"], routing)
    with pytest.raises(ValueError, match="reported none"):
        check.require_routing(conf, wl["limits"], {})
    for k in check.ROUTED_NUMBERS:
        limits = {n: v for n, v in wl["limits"].items() if n != k}
        with pytest.raises(ValueError, match=k):
            check.require_routing(conf, limits, routing)
    dwl, dconf, _ = tiny_cell.tiny()
    check.require_routing(dconf, dwl["limits"], {})
