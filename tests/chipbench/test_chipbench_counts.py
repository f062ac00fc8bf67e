"""Counts the chip benchmark computes on the host: model FLOPs, HLO
collective and kernel bytes, the peaks table, and the token traffic."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import flops, hlo, peaks, traffic  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIGS = ROOT / "benchmarks" / "chip" / "configs"


def _conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qwen_flops_match_a_hand_count():
    conf = _conf("qwen2.5-14b.l1")
    # wq 5120x5120 + wk, wv 5120x1024 each + wo 5120x5120; SwiGLU 3 x
    # 5120x13824; the head over 19,008 ids (biases are no matmuls)
    attn = 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120      #  62,914,560
    mlp = 3 * 5120 * 13824                                  # 212,336,640
    head = 5120 * 19008                                     #  97,320,960
    params = attn + mlp + head
    assert params == 372_572_160
    assert flops.matmul_params_per_token(conf) == params
    # causal attention: 3 x 2 x T x (H x hd) per layer, T = 4096
    attn_flops = 3 * 2 * 4096 * 40 * 128                    # 125,829,120
    assert flops.train_flops_per_token(conf, 4096) == 6 * params + attn_flops
    assert flops.train_flops_per_token(conf, 4096) == 2_361_262_080


def test_granite_flops_match_a_hand_count():
    """The sparse-expert count, at Granite-3.0-1B-A400M's widths and 8
    layers: the router and the top-k experts, no capacity padding."""
    conf = dict(hidden_size=1024, num_attention_heads=16,
                num_key_value_heads=8, head_dim=64, intermediate_size=512,
                num_local_experts=32, num_experts_per_tok=8,
                vocab_size=49155, num_hidden_layers=8)
    # per layer: wq 1024x1024 + wk, wv 1024x512 each + wo 1024x1024
    attn = 1024 * 1024 + 2 * 1024 * 512 + 1024 * 1024       # 3,145,728
    router = 1024 * 32                                      #    32,768
    experts = 8 * 3 * 1024 * 512                            # 12,582,912
    head = 1024 * 49155                                     # 50,334,720
    params = 8 * (attn + router + experts) + head           # 176,425,984
    assert params == 176_425_984
    assert flops.matmul_params_per_token(conf) == params
    # causal attention: 3 x 2 x T x (H x hd) per layer, T = 4096
    attn_flops = 8 * 3 * 2 * 4096 * 16 * 64                 # 201,326,592
    assert flops.train_flops_per_token(conf, 4096) == 6 * params + attn_flops
    assert flops.train_flops_per_token(conf, 4096) == 1_259_882_496


def test_collective_bytes_of_the_four_chip_step():
    """A trimmed copy of the compiled 24-layer data=4 step for a v5e:2x2:
    the layer scans run 24 trips, the forward and the rematerialised
    backward each gather 'layers' (3,180,544 bf16) and 'layers_experts'
    (50,331,648 bf16) per layer, the entry gathers 'globals' once."""
    text = (DATA / "zero3x4_step.hlo.txt").read_text()
    got = hlo.collective_bytes(text)
    per_layer = 2 * (3_180_544 + 50_331_648)
    assert got["all-gather"] == (4 * 24 + 1,
                                 2 * 24 * per_layer + 2 * 100_670_464)
    calls, nbytes = got["all-reduce"]
    assert calls == 24 + 3
    # per layer one all-reduce of both gradient groups; 'globals' once;
    # two scalar reductions
    assert nbytes == 24 * per_layer + 2 * 100_670_464 + 3 * 4
    assert "reduce-scatter" not in got


def test_kernel_bytes_of_the_four_chip_step():
    text = (DATA / "zero3x4_step.hlo.txt").read_text()
    got = hlo.custom_calls(text)
    # reads w g m v mask, writes w m v: 32 B per element of a shard, plus
    # the 8 fp32 scalars
    shard = {"adamw_store_update.5": 24 * 12_582_912,
             "adamw_store_update.4": 24 * 795_136,
             "adamw_store_update.3": 196_622 * 128}
    assert got == {n: {"bytes": 32 * e + 32, "calls": 1}
                   for n, e in shard.items()}


def test_collective_op_names_cover_every_half():
    names = hlo.collective_op_names(
        (DATA / "zero3x4_step.hlo.txt").read_text())
    assert {"async-collective-start.1", "async-collective-done.1",
            "all-reduce.84", "fusion.913"} <= names
    assert not any(n.startswith("adamw") for n in names)


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def _stream_loop(vocab, seq, rows, seed, order_mix=0.7):
    """The distribution drawn one position at a time."""
    rng = np.random.default_rng(seed)
    a = int(rng.integers(3, 97)) * 2 + 1
    b = int(rng.integers(0, vocab))
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]
    iid = np.minimum(np.searchsorted(cdf, rng.random((rows, seq))),
                     vocab - 1)
    follow = rng.random((rows, seq)) < order_mix
    toks = np.empty((rows, seq), np.int64)
    toks[:, 0] = iid[:, 0]
    for t in range(1, seq):
        succ = (a * toks[:, t - 1] + b) % vocab
        toks[:, t] = np.where(follow[:, t], succ, iid[:, t])
    return toks


def test_token_pool_matches_the_stepwise_chain():
    seed = 2 ** 35 + 17
    got = traffic.token_pool(49155, 300, 5, seed)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _stream_loop(49155, 300, 5, seed))
    np.testing.assert_array_equal(got, traffic.token_pool(49155, 300, 5,
                                                          seed))
    assert len({r.tobytes() for r in got}) == 5


def test_batch_pool_shape_and_distinct_batches():
    traf = traffic.load("b1s4096")
    pool = traffic.batch_pool(dict(traf, seq=64), 1000, 4, 3)
    assert pool.shape == (traf["pool"], 4, 64)
    assert len({b.tobytes() for b in pool}) == traf["pool"]
