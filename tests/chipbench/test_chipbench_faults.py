"""A whole run of the tiny cell, with the look for a chip skipped:
``correct`` holds on the sound program and comes out false with the
timed path broken underneath -- a step that returns its state
unchanged, and half of the batch left out with the mean taken over the
rest."""
import pathlib
import sys
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import tiny_cell  # noqa: E402
from benchmarks.chip import cell  # noqa: E402

SEED = tiny_cell.SEEDS[0]


def _run():
    wl, conf, traf = tiny_cell.tiny()
    return cell.run("tiny", SEED, 0.5, False, t_start=time.perf_counter(),
                    require_tpu=False, wl=wl, conf=conf, traf=traf)


def _unchanged(self, runtime, params, grads, state, step):
    return params, state


def _half_batch(loss):
    def wrapped(self, pg, batch):
        tokens = batch["tokens"]
        B, T = tokens.shape
        cut = tokens[:B // 2] if B >= 2 else tokens[:, :T // 2]
        return loss(self, pg, dict(batch, tokens=cut))
    return wrapped


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 3
    assert set(r["metrics"]) == {"tokens_per_s", "step_ms_p90",
                                 "hbm_peak_gb", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_fault_is_not_correct(fault, monkeypatch):
    from repro.models.transformer import DecoderLM
    from repro.optim.adamw import AdamW

    if fault == "unchanged_state":
        monkeypatch.setattr(AdamW, "update", _unchanged)
    else:
        monkeypatch.setattr(DecoderLM, "loss", _half_batch(DecoderLM.loss))
    r = _run()
    assert not r["correct"], r["checks"]
    if fault == "unchanged_state":
        assert r["checks"]["change_gap"]["value"] == pytest.approx(1.0)
