"""Whole runs of the tiny routed cell, with the look for a chip skipped
and the program's expert choices reported (``routing_report.py``):
``correct`` holds on the sound program and comes out false with the
timed path broken underneath -- a step that returns its state
unchanged, half of the batch left out of the loss with the mean taken
over the rest, an expert choice altered where the router makes it, every
choice dropped, and the capacity counted in reverse token order.  A
routed cell whose step reports no routing ends in an error."""
import pathlib
import sys
import time

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import routing_report  # noqa: E402
import tiny_cell  # noqa: E402
from benchmarks.chip import cell  # noqa: E402

SEED = tiny_cell.SEEDS[1]


@pytest.fixture(autouse=True)
def _routed_reference(monkeypatch):
    tiny_cell.use_routed_reference(monkeypatch)


def _run():
    wl, conf, traf = tiny_cell.tiny_routed()
    return cell.run("tiny-routed", SEED, 0.5, False,
                    t_start=time.perf_counter(), require_tpu=False, wl=wl,
                    conf=conf, traf=traf)


def _unchanged(self, runtime, params, grads, state, step):
    return params, state


def _half_of_the_loss(ce):
    """The cross entropy over the first half of the positions alone."""
    def wrapped(logits, labels, mask, **kw):
        T = mask.shape[1]
        keep = (jnp.arange(T) < T // 2)[None].astype(mask.dtype)
        return ce(logits, labels, mask * keep, **kw)
    return wrapped


def _ranks_reversed(ranks):
    """Each choice's rank within its expert counted from the last token."""
    def wrapped(flat_e, n_experts):
        return ranks(flat_e[::-1], n_experts)[::-1]
    return wrapped


def _ranks_past_capacity(ranks):
    """Every rank past any capacity: every choice dropped."""
    def wrapped(flat_e, n_experts):
        return ranks(flat_e, n_experts) + flat_e.shape[0]
    return wrapped


class _LastChoiceWorst:
    """``lax`` with a ``top_k`` whose last choice is the least likely
    expert."""

    def __init__(self, lax):
        self._lax = lax

    def top_k(self, probs, k):
        p, e = self._lax.top_k(probs, k)
        worst = jnp.argmin(probs, axis=-1)
        e = e.at[:, -1].set(worst)
        return jnp.take_along_axis(probs, e, axis=-1), e

    def __getattr__(self, name):
        return getattr(self._lax, name)


def test_sound_routed_run_is_correct(monkeypatch):
    routing_report.install_in_program(monkeypatch, cell.Program)
    r = _run()
    assert r["correct"], r["checks"]
    assert r["checks"]["route_gap"]["value"] == 0.0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_choice", "all_dropped",
                                   "drop_order_reversed"])
def test_routed_fault_is_not_correct(fault, monkeypatch):
    from repro.models import layers, moe
    from repro.optim.adamw import AdamW

    # planted before the report wraps the program's ranks, so the report
    # carries the drops that the faulty program made
    ranks = moe._positions_within_expert
    if fault == "unchanged_state":
        monkeypatch.setattr(AdamW, "update", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(layers, "vocab_parallel_ce",
                            _half_of_the_loss(layers.vocab_parallel_ce))
    elif fault == "altered_choice":
        monkeypatch.setattr(moe, "lax", _LastChoiceWorst(moe.lax))
    elif fault == "all_dropped":
        monkeypatch.setattr(moe, "_positions_within_expert",
                            _ranks_past_capacity(ranks))
    else:
        monkeypatch.setattr(moe, "_positions_within_expert",
                            _ranks_reversed(ranks))
    routing_report.install_in_program(monkeypatch, cell.Program)
    r = _run()
    assert not r["correct"], r["checks"]
    if fault == "altered_choice":
        assert r["checks"]["route_gap"]["value"] > 0.25
    if fault in ("all_dropped", "drop_order_reversed"):
        assert r["checks"]["kept_gap"]["value"] > 0
    if fault == "all_dropped":
        assert r["checks"]["route_gap"]["value"] == 1.0


def test_routed_cell_without_the_steps_routing_is_an_error():
    with pytest.raises(ValueError, match="reported none"):
        _run()
