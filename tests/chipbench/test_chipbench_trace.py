"""Trace reduction of the chip benchmark: busy union, kernel time by
name, collective time with no compute beside it, and idle gaps named by
the host span that covers them -- on hand-made intervals, and on a
trimmed trace recorded on a TPU v5e."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace as tr  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _hand_trace():
    ops = {0: [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 2.0),
               ("all-gather.3", 1.5, 3.0), ("adamw_store_update.4", 3.0,
                                            3.5), ("fusion.5", 4.5, 5.0)],
           1: [("fusion.1", 0.0, 4.0)]}
    spans = [("bench.window", 0.0, 5.0), ("bench.place", 3.4, 4.2),
             ("bench.dispatch", 4.2, 4.3), ("bench.wait", 0.0, 3.0)]
    return tr.Trace(ops, spans)


def test_union_and_busy():
    t = _hand_trace()
    assert tr.union([(0, 1), (0.5, 2), (3, 4), (4, 4.5)]) == [(0, 2),
                                                              (3, 4.5)]
    assert tr.busy(t, t.window()) == {0: pytest.approx(4.0),
                                      1: pytest.approx(4.0)}
    assert tr.busy(t, (1.0, 2.0)) == {0: pytest.approx(1.0),
                                      1: pytest.approx(1.0)}


def test_kernel_time_by_name():
    t = _hand_trace()
    got = tr.op_time(t, t.window(), {"adamw_store_update.4"})
    assert got == {0: pytest.approx(0.5), 1: 0.0}


def test_collective_time_with_no_compute():
    t = _hand_trace()
    got = tr.exposed(t, t.window(), lambda n: n.startswith("all-gather"))
    # the gather runs 1.5..3.0; fusion.2 covers it to 2.0
    assert got == {0: pytest.approx(1.0), 1: 0.0}


def test_idle_gaps_named_by_host_span():
    t = _hand_trace()
    gaps = tr.idle_gaps(t, t.window(), 0)
    assert [(n, pytest.approx(g)) for n, g, _, _ in gaps] == [
        ("bench.place", 1.0)]
    t.ops[0].remove(("fusion.5", 4.5, 5.0))
    t.ops[0] += [("fusion.6", 4.5, 4.7), ("fusion.7", 4.7 + 5e-7, 5.0)]
    gaps = tr.idle_gaps(t, t.window(), 0)
    assert [n for n, *_ in gaps] == ["bench.place", "device.between_ops"]
    assert gaps[1][1] == pytest.approx(5e-7)
    assert tr.top([("a", 1.0), ("b", 3.0), ("a", 2.5)]) == [["a", 3.5],
                                                             ["b", 3.0]]


def test_json_round_trip():
    t = _hand_trace()
    back = tr.Trace.from_json(t.to_json())
    assert back.ops == t.ops and back.spans == t.spans


def test_reduction_of_a_chip_trace():
    """70 ms around the optimizer of one Granite 8-layer step on a TPU v5e
    (one chip, ``bench.window`` cut to 0.56..0.63 s): the three fused
    AdamW calls of the step, and a device that never empties because the
    host runs a step ahead."""
    t = tr.Trace.from_json((DATA / "l8_step_tail.trace.json").read_text())
    win = t.window()
    assert win == (0.56, 0.63)
    busy = tr.busy(t, win)[0]
    assert busy == pytest.approx(0.069979678, abs=1e-8)
    adamw = tr.op_time(t, win, lambda n: n.startswith("adamw_store_update"))
    # 8.234938 + 1.923996 + 31.997847 ms: globals, layers, layers_experts
    assert adamw[0] == pytest.approx(0.042156781, abs=1e-8)
    assert sorted(n for n, s, _ in t.ops[0] if n.startswith("adamw")) == [
        "adamw_store_update.3", "adamw_store_update.4",
        "adamw_store_update.5"]
    gaps = tr.idle_gaps(t, win, 0)
    assert sum(g for _, g, _, _ in gaps) == pytest.approx(win[1] - win[0]
                                                          - busy, abs=1e-9)
    # the two gaps of 9 and 11 us fall while the host waits on a loss;
    # the rest are nanoseconds between two operations
    assert {n for n, *_ in gaps} == {"bench.wait", "device.between_ops"}
    long = sorted(g for n, g, _, _ in gaps if n == "bench.wait")
    assert long == [pytest.approx(9.157e-6, abs=1e-9),
                    pytest.approx(1.067e-5, abs=1e-9)]
