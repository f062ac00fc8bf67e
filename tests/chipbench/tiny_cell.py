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


# The program's granite-moe-1b-a400m as it stands (no multipliers, RoPE
# theta 1e6, untied head, capacity factor 1.25, load-balance coefficient
# 0.01), cut to the CPU: 2 layers, 8 experts, top-2, 256 tokens a row.
# Its reference is the program's MoE decoder (moe_decoder_ref.py), which
# ``use_routed_reference`` puts in the harness's hands.
ROUTED_CONF = {
    "name": "granite-moe-tiny", "reference": "moe_decoder",
    "source": "the program's registry entry granite-moe-1b-a400m",
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 64, "vocab_size": 512, "num_hidden_layers": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2,
    "capacity_factor": 1.25, "router_aux_loss_coef": 0.01,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "tie_word_embeddings": False,
    "assumed": {"head_dim": 32, "qkv_bias": False},
    "program": {"registry": "granite-moe-1b-a400m",
                "replace": dict(n_layers=2, d_model=128, n_heads=4,
                                n_kv_heads=2, head_dim=32, d_ff=64,
                                vocab=512, n_experts=8, top_k=2)},
}
# CPU readings at this size with the program's routing handed over: over
# 18 seeds the program reads at most 9.3e-4 / 0.063 / 3.5e-3 (loss /
# worst-leaf gradient / worst-leaf change) and its largest route margin
# is 0.42 logits; the fp8 control, routing itself, at least 5.7e-3 /
# 0.064 / 0.012 (seeds 11, 1011, ..., 5011); the routing fault a
# route_gap of 0.617 and 0.625 (seeds 11, 1011).  kept_gap is an exact
# comparison: the program's drops against the capacity of the reference's
# model over the same experts.
# The worst gradient leaf is an attention key or query of the second
# layer on most seeds, and the control's lowest sits on the program's
# highest, so grad_gap is not compared here.
ROUTED_LIMITS = {"loss_gap": 0.0025, "change_gap": 0.006, "route_gap": 0.0,
                 "kept_gap": 0.0}


def use_routed_reference(monkeypatch) -> None:
    """Have the harness take ``moe_decoder_ref`` as the tiny routed
    configuration's reference (it is no file of ``references/``)."""
    import moe_decoder_ref
    from benchmarks.chip import check

    plain = check.reference_module

    def reference_module(conf):
        if conf["name"] == ROUTED_CONF["name"]:
            return moe_decoder_ref
        return plain(conf)

    monkeypatch.setattr(check, "reference_module", reference_module)


def tiny_routed(microbatches: int = 1, rows: int = 1):
    """(workload, configuration, traffic) dicts of the tiny routed cell:
    ``rows`` rows of 256 tokens a step in ``microbatches``."""
    wl, _, traf = cell.load(CELL)
    conf = copy.deepcopy(ROUTED_CONF)
    traf = dict(traf, seq=256, pool=4, batch_per_chip=rows)
    wl = dict(wl, name="granite-moe-tiny.train", config=conf["name"],
              chips=1, mesh={"data": 1, "model": 1},
              microbatches=microbatches, limits=dict(ROUTED_LIMITS),
              trace_seconds=1)
    return wl, conf, traf
