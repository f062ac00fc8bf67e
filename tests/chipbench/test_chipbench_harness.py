"""The chip benchmark's harness: cells, configurations, traffic and
metrics found by name in files of their own; no result without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import cell, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = BENCH["workloads"][0]["name"]


def test_every_benchmark_entry_has_its_file():
    for w in BENCH["workloads"]:
        wl, conf, traf = cell.load(w["name"])
        assert wl["config"] == w["config"] == conf["name"]
        assert wl["traffic"] == w["traffic"] and wl["chips"] == w["chips"]
        assert set(wl["limits"]) <= {"loss_gap", "grad_gap", "change_gap",
                                     "grad_median_gap", "route_gap",
                                     "kept_gap", "dropped_share"}
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
    for m in BENCH["per_layer"]:
        assert callable(cell._load_metric(m["name"]).read)


def test_program_config_matches_each_configuration_file():
    for w in BENCH["workloads"]:
        wl, conf, _ = cell.load(w["name"])
        cfg = cell.program_config(conf, wl)
        assert cfg.n_layers == conf["num_hidden_layers"]


def test_a_new_workload_file_is_found_by_name(tmp_path, monkeypatch):
    """A later cell is a new file under workloads/ (and, if it needs
    them, new files under configs/ and traffic/) plus its entry in
    BENCHMARK.json: nothing else changes."""
    here = tmp_path / "chip"
    for sub in ("workloads", "configs", "traffic"):
        shutil.copytree(cell.HERE / sub, here / sub)
    wl = json.loads((cell.HERE / "workloads" / f"{CELL}.json").read_text())
    wl.update(name="qwen2.5-14b.l1.micro4.b4s4096", microbatches=4,
              traffic="b4s4096")
    (here / "workloads" / f"{wl['name']}.json").write_text(json.dumps(wl))
    traf = traffic.load("b1s4096")
    (here / "traffic" / "b4s4096.json").write_text(
        json.dumps(dict(traf, batch_per_chip=4)))
    monkeypatch.setattr(cell, "HERE", here)
    monkeypatch.setattr(traffic, "HERE", here)
    got, conf, traf = cell.load("qwen2.5-14b.l1.micro4.b4s4096")
    assert got["microbatches"] == 4 and traf["batch_per_chip"] == 4
    assert cell.program_config(conf, got).parallel.microbatches == 4
    with pytest.raises(FileNotFoundError):
        cell.load("no-such-cell")


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_step_ms_p90_takes_samples_of_a_quarter_second_or_more():
    """Steps shorter than the host clock can time alone are timed in
    groups that span 250 ms; a stall in one group shows in its sample."""
    import numpy as np

    steady = np.cumsum(np.r_[0.0, np.full(60, 0.1)])
    assert cell.step_ms_p90(steady) == pytest.approx(100.0)
    dts = np.full(60, 0.1)
    dts[::6] += 0.09   # a stall every sixth step: in every other group
    got = cell.step_ms_p90(np.cumsum(np.r_[0.0, dts]))
    assert got == pytest.approx((0.19 + 0.1 + 0.1) / 3 * 1e3)
    long_steps = np.cumsum(np.r_[0.0, np.full(20, 0.5)])
    assert cell.step_ms_p90(long_steps) == pytest.approx(500.0)
