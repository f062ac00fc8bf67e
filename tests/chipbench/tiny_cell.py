"""A cell of the chip benchmark cut to a size the CPU runs in seconds:
the Qwen2 structure of the chip cell (GQA with q, k, v biases, SwiGLU,
untied head) at toy widths, 2 layers, 256 tokens a row.  Its limits are
set from CPU readings of this size (program against reference, and the
fp8 control), not from the chip cell's."""
import copy
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import cell  # noqa: E402

CELL = "qwen2.5-14b.l1.train.b1s4096"
SIZES = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=256, vocab_size=512, num_hidden_layers=2)
PROGRAM = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
               head_dim=32, d_ff=256, vocab=512, norm_eps=1e-5)
# CPU readings at this size (seeds 11, 1011, 2011), loss / worst-leaf
# gradient / worst-leaf change gaps: the program reads at most 8.6e-4 /
# 6.7e-3 / 6.8e-3 (a reference with bfloat16 matmuls reads alike); the
# fp8 control at least 6.3e-3 / 0.031 / 0.011; half the batch left out
# at least 0.0099 / 0.40 / 0.20; a state left unchanged reads 1 on the
# change.  These limits hold for this size and are no chip cell's.
SEEDS = (11, 1011, 2011)
LIMITS = {"loss_gap": 0.003, "grad_gap": 0.015, "change_gap": 0.05}


def tiny(chips: int = 1, seq: int = 256):
    """(workload, configuration, traffic) dicts of the tiny cell."""
    wl, conf, traf = cell.load(CELL)
    conf = copy.deepcopy(conf)
    conf.update(SIZES)
    conf["assumed"]["head_dim"] = 32
    conf["program"]["replace"] = dict(PROGRAM)
    traf = dict(traf, seq=seq, pool=4)
    wl = dict(wl, chips=chips, mesh={"data": chips, "model": 1},
              limits=dict(LIMITS), trace_seconds=1)
    return wl, conf, traf
