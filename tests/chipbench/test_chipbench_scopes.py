"""The program's names for its work and the per-layer metrics that read
them: scopes parsed from HLO ``op_name`` paths, device time by scope,
the ``train.*`` host spans, the tiny cell's step compiled on the CPU,
and the recorded fixtures read as before."""
import hashlib
import json
import pathlib
import re
import sys
import time
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tiny_cell  # noqa: E402
from benchmarks.chip import cell, hlo, scopes  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

DATA = HERE / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["fsdp.share_pct", "model.embed.share_pct", "model.attn.share_pct",
       "model.mlp.share_pct", "model.head_loss.share_pct",
       "step.remat.share_pct", "optim.share_pct", "optim.wd_mask.share_pct",
       "train.host_ms_per_step"]

HLO = """\
HloModule jit_step_fn

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step_fn)/jit(main)/shard_map/transpose(jvp(model.embed))/mul"}
}

ENTRY %main (a: f32[8,8], b: f32[8]) -> f32[8] {
  %a = f32[8,8]{1,0} parameter(0)
  %b = f32[8]{0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_fn)/jit(main)/shard_map/jvp()/while/body/closed_call/model.attn/dot_general"}
  %dot.2 = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_fn)/jit(main)/shard_map/transpose(jvp())/while/body/closed_call/checkpoint/model.mlp/dot_general"}
  %dot.3 = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_fn)/jit(main)/shard_map/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/model.attn/dot_general"}
  %select.4 = f32[8]{0} select(%b, %b, %b), metadata={op_name="jit(step_fn)/jit(main)/shard_map/optim.update/optim.wd_mask/select_n"}
  %adamw_store_update.5 = f32[8]{0} custom-call(%b, %select.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jit(main)/shard_map/optim.update/jit(adamw_store_update)/adamw_store_update/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jit(main)/shard_map/transpose(jvp(model.embed))/mul"}
  ROOT %copy.7 = f32[8]{0} copy(%fusion.6)
}
"""


def test_op_scopes_of_each_pass():
    got = scopes.op_scopes(HLO)
    assert got["dot.1"] == (("model.attn",), False)
    assert got["dot.2"] == (("model.mlp",), False)
    assert got["dot.3"] == (("model.attn",), True)
    assert got["select.4"] == (("optim.update", "optim.wd_mask"), False)
    assert got["adamw_store_update.5"] == (("optim.update",), False)
    # a scope inside a transform's parentheses, and a fused instruction
    assert got["fusion.6"] == (("model.embed",), False)
    assert got["multiply.9"] == (("model.embed",), False)
    assert got["copy.7"] == ((), False)
    assert got["a"] == ((), False)


def test_op_labels_keep_pass_and_primitive_under_a_scope():
    assert {k: v for k, v in hlo.op_labels(HLO).items() if "." in k} == {
        "dot.1": "fwd/dot_general", "dot.2": "bwd/dot_general",
        "dot.3": "bwd/dot_general", "select.4": "step/select_n",
        "adamw_store_update.5": "adamw_store_update",
        "fusion.6": "bwd/mul", "multiply.9": "bwd/mul", "copy.7": "step/copy"}


def _hand_ctx(with_names: bool = True):
    """Two chips; window 0..10 s; three steps."""
    ops = {0: [("dot.1", 0.0, 2.0), ("dot.2", 2.0, 3.0), ("dot.3", 3.0, 4.0),
               ("select.4", 4.0, 5.0), ("adamw_store_update.5", 5.0, 7.0),
               ("fusion.6", 7.0, 7.5), ("copy.7", 7.5, 8.0),
               ("dot.1", 9.5, 11.0)],
           1: [("dot.1", 0.0, 4.0), ("copy.7", 4.0, 8.0)]}
    spans = [("bench.window", 0.0, 10.0), ("bench.place", 8.0, 8.2),
             ("bench.dispatch", 8.2, 8.5)]
    program = [("train.step", 0.5, 9.0), ("train.batch", 0.5, 0.6),
               ("train.dispatch", 0.6, 0.7), ("train.batch", 4.0, 4.3),
               ("train.dispatch", 4.3, 4.4), ("train.batch", 9.9, 10.1),
               ("train.dispatch", 10.2, 10.3)]
    sc = scopes.op_scopes(HLO) if with_names else {
        n: ((), r) for n, (_, r) in scopes.op_scopes(HLO).items()}
    return types.SimpleNamespace(
        trace=tr.Trace(ops, spans), window=(0.0, 10.0), window_s=10.0,
        steps=3, chips=2, scopes=sc,
        program_spans=program if with_names else [])


def test_scope_time_clips_to_the_window():
    ctx = _hand_ctx()
    attn = scopes.scope_time(ctx.trace, ctx.window, ctx.scopes,
                             scopes.in_any("model.attn"))
    # dot.1 twice on chip 0 (0..2 and 9.5..10 in the window), dot.3 once
    assert attn == {0: pytest.approx(3.5), 1: pytest.approx(4.0)}
    remat = scopes.scope_time(ctx.trace, ctx.window, ctx.scopes,
                              lambda _, r: r)
    assert remat == {0: pytest.approx(1.0), 1: 0.0}


def test_each_new_metric_on_a_hand_trace():
    ctx = _hand_ctx()
    got = {m: cell._load_metric(m).read(ctx) for m in NEW}
    # busy: chip 0 8.5 s (0..8 and 9.5..10), chip 1 8 s
    want = {"fsdp.share_pct": 0.0,
            "model.embed.share_pct": 50 * (0.5 / 8.5),
            "model.attn.share_pct": 50 * (3.5 / 8.5 + 4.0 / 8.0),
            "model.mlp.share_pct": 50 * (1.0 / 8.5),
            "model.head_loss.share_pct": 0.0,
            "step.remat.share_pct": 50 * (1.0 / 8.5),
            "optim.share_pct": 50 * (3.0 / 8.5),
            "optim.wd_mask.share_pct": 50 * (1.0 / 8.5),
            # batch + dispatch spans that start in the window, per step
            "train.host_ms_per_step": 1e3 * (0.1 + 0.1 + 0.3 + 0.1 + 0.2)
            / 3}
    assert got == {m: pytest.approx(v) for m, v in want.items()}


def test_a_program_without_names_gives_nothing_to_read():
    """The older program: no scope and no ``train.*`` span.  Only the
    remat share, which reads JAX's own ``rematted_computation``, reads."""
    ctx = _hand_ctx(with_names=False)
    got = {m: cell._load_metric(m).read(ctx) for m in NEW}
    assert got.pop("step.remat.share_pct") == pytest.approx(50 / 8.5)
    assert set(got.values()) == {None}


def test_context_is_read_from_the_harness_frame(monkeypatch):
    read = []
    monkeypatch.setattr(scopes, "load_program_spans", lambda path: (
        read.append(path) or [("train.batch", 0.0, 0.5)]))

    def reduce_trace_like(ctx):
        # the locals that ``cell.reduce_trace`` holds beside ``ctx``
        text, paths = HLO, ["a.xplane.pb", "b.xplane.pb"]  # noqa: F841
        return cell._load_metric("train.host_ms_per_step").read(ctx)

    ctx = types.SimpleNamespace(window=(0.0, 2.0), steps=1)
    assert reduce_trace_like(ctx) == pytest.approx(500.0)
    assert read == ["b.xplane.pb"]
    assert ctx.scopes == scopes.op_scopes(HLO)
    assert reduce_trace_like(ctx) == pytest.approx(500.0)
    assert read == ["b.xplane.pb"]      # read once per namespace
    alone = types.SimpleNamespace(window=(0.0, 2.0), steps=1)
    assert cell._load_metric("train.host_ms_per_step").read(alone) is None
    assert alone.scopes == {} and alone.program_spans == []


def test_benchmark_entries_of_the_new_metrics():
    got = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = got[name]
        assert m["moves"] == "tokens_per_s" and m["source"] == "program_span"
        assert m["workloads"] == ["qwen2.5-14b.l1.train.b1s4096"]
        assert m["unit"] == ("ms" if name.endswith("_ms_per_step") else "%")


def test_metric_files_name_what_the_program_names():
    from repro import spans

    named = set()
    for name in NEW:
        text = (cell.HERE / "metrics" / f"{name}.py").read_text()
        named |= set(re.findall(r'"((?:model|fsdp|optim|train)\.\w+)"',
                                text))
    assert named == set(spans.DEVICE_SCOPES) | {spans.TRAIN_BATCH,
                                                spans.TRAIN_DISPATCH}
    assert scopes.SPAN_PREFIX == "train."
    assert all(s.startswith("train.") for s in spans.HOST_SPANS)


@pytest.fixture(scope="module")
def tiny_step_hlo():
    import jax

    from benchmarks.chip import traffic

    wl, conf, traf = tiny_cell.tiny()
    prog = cell.Program(wl, conf, traf, jax.devices())
    pool = traffic.batch_pool(traf, conf["vocab_size"], 1, 5)
    params, opt_state = prog.init_state(5)
    return prog.compile(params, opt_state, prog.place(pool[0])).as_text()


def test_every_scope_labels_the_tiny_step(tiny_step_hlo):
    from repro import spans

    got = scopes.op_scopes(tiny_step_hlo)
    seen = {s for sc, _ in got.values() for s in sc}
    assert seen == set(spans.DEVICE_SCOPES)
    assert any(remat for _, remat in got.values())
    for name, (sc, _) in got.items():
        # model.*, fsdp.* and optim.update never nest in one another
        assert len(set(sc) - {"optim.wd_mask"}) <= 1, (name, sc)
        if "optim.wd_mask" in sc:
            assert "optim.update" in sc


def test_no_matmul_outside_a_program_scope(tiny_step_hlo):
    paths = {}
    for line in tiny_step_hlo.splitlines():
        m = re.search(r'%(\S+) = .*op_name="([^"]*/dot_general)"', line)
        if m:
            paths[m.group(1)] = m.group(2)
    assert paths
    got = scopes.op_scopes(tiny_step_hlo)
    assert [p for n, p in paths.items() if not got[n][0]] == []


def test_traced_tiny_run_reads_the_program_spans():
    """A traced run of the tiny cell on the CPU: the metrics find the
    step's HLO and the trace through the harness, and the ``train.*``
    spans are in the trace.  A CPU trace has no TPU device plane, so the
    device shares read nothing."""
    wl, conf, traf = tiny_cell.tiny()
    entries = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    r = cell.run("tiny-scopes", tiny_cell.SEEDS[0], 0.5, True,
                 t_start=time.perf_counter(), require_tpu=False, wl=wl,
                 conf=conf, traf=traf, per_layer=entries)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"train.host_ms_per_step"}
    assert r["metrics"]["train.host_ms_per_step"]["value"] > 0


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()


def test_recorded_fixtures_read_as_before():
    """The labels of the recorded four-chip HLO and the metrics and idle
    gaps of the recorded Granite trace, as the harness read them before
    the program named its work."""
    text = (DATA / "zero3x4_step.hlo.txt").read_text()
    assert _digest(sorted(hlo.op_labels(text).items())) == (
        "5f0418fe8884f5e9a6d992c9a78cc3b5efef2927d6a11e07fab47ee49bcdce96")
    t = tr.Trace.from_json((DATA / "l8_step_tail.trace.json").read_text())
    win = t.window()
    ctx = types.SimpleNamespace(
        trace=t, window=win, window_s=win[1] - win[0], steps=1,
        kernels={f"adamw_store_update.{i}": {} for i in (3, 4, 5)})
    got = {m: cell._load_metric(m).read(ctx) for m in (
        "device.idle_pct", "adamw_store_update.share_pct",
        "driver.host_ms_per_step")}
    assert got == {"device.idle_pct": pytest.approx(0.0290314285690707),
                   "adamw_store_update.share_pct": pytest.approx(
                       60.2414618140984),
                   "driver.host_ms_per_step": pytest.approx(
                       1.1124799999999713)}
    assert tr.top((n, g) for n, g, _, _ in tr.idle_gaps(t, win, 0)) == [
        ["bench.wait", pytest.approx(1.9827e-05, abs=1e-10)],
        ["device.between_ops", pytest.approx(4.95e-07, abs=1e-10)]]


def _recorded():
    """The first two steps of a traced window of the Qwen2.5-14B one-layer
    cell on a TPU v5e with the scoped program: device ops, ``bench.*``
    spans (``bench.window`` cut to the two steps), ``train.*`` spans, the
    device's module runs, and the scopes, labels and kernel bytes of the
    instructions in it, read from the compiled step's HLO."""
    raw = json.loads((DATA / "qwen_l1_two_steps.trace.json").read_text())
    t = tr.Trace.from_json(json.dumps(raw))
    win = t.window()
    from benchmarks.chip import peaks

    ctx = types.SimpleNamespace(
        trace=t, window=win, window_s=win[1] - win[0], steps=raw["steps"],
        chips=1, kernels=raw["kernels"], peak=peaks.peaks("TPU v5 lite"),
        flops_per_step=raw["flops_per_step"],
        scopes={n: (tuple(s), r) for n, (s, r) in raw["scopes"].items()},
        program_spans=[tuple(s) for s in raw["program_spans"]])
    return raw, ctx


def test_reduction_of_a_scoped_chip_trace():
    raw, ctx = _recorded()
    got = {m["name"]: cell._load_metric(m["name"]).read(ctx)
           for m in BENCH["per_layer"]}
    assert got == {
        "device.idle_pct": pytest.approx(0.30166013),
        "step_mfu": pytest.approx(14.24043033),
        "adamw_store_update_roofline": pytest.approx(47.78714094),
        "adamw_store_update.share_pct": pytest.approx(11.17820860),
        "driver.host_ms_per_step": pytest.approx(1.68308),
        "fsdp.share_pct": pytest.approx(14.48997832),
        "model.embed.share_pct": pytest.approx(10.07481409),
        "model.attn.share_pct": pytest.approx(26.76767897),
        "model.mlp.share_pct": pytest.approx(13.06662592),
        "model.head_loss.share_pct": pytest.approx(5.14704906),
        "step.remat.share_pct": pytest.approx(20.14493494),
        "optim.share_pct": pytest.approx(23.46731713),
        "optim.wd_mask.share_pct": pytest.approx(12.28900758),
        "train.host_ms_per_step": pytest.approx(1.73495)}
    # the disjoint top-level scopes hold 93% of the busy time; the rest is
    # mostly XLA's layout copies of w, m, v for the AdamW kernel
    cover = scopes.share(ctx, lambda sc, _: bool(set(sc) - {"optim.wd_mask"}))
    assert cover == pytest.approx(93.0134635)
    # the breakdown's labels and idle gaps, as the harness names them
    win = ctx.window
    ops = [(raw["labels"].get(n, n), e - s)
           for n, s, e in tr.clip_named(ctx.trace.ops[0], *win)]
    assert [n for n, _ in tr.top(ops, 5)] == [
        "bwd/dot_general", "step/select_n", "adamw_store_update",
        "bwd/scatter-add", "fwd/dot_general"]
    assert [n for n, *_ in tr.top(
        (n, g) for n, g, _, _ in tr.idle_gaps(ctx.trace, win, 0))] == [
        "bench.dispatch", "bench.wait", "device.between_ops"]


def test_host_and_device_share_one_clock():
    """Step i's ``train.dispatch`` begins before the first device op of
    step i, and no device op of the window runs before the first
    dispatch: host spans and device ops sit on one clock."""
    raw, ctx = _recorded()
    dispatch = sorted(s for n, s, _ in ctx.program_spans
                      if n == "train.dispatch")
    runs = sorted((s, e) for n, s, e in raw["modules"]
                  if n.startswith("jit_step_fn"))
    ops = ctx.trace.ops[0]
    assert len(runs) == 2 and len(dispatch) == 3
    for d, (s, e) in zip(dispatch, runs):
        first = min(o for _, o, _ in ops if s <= o < e)
        assert d < s <= first
    assert min(s for _, s, _ in ops) > dispatch[0]
    # the spans nest: each step holds its batch, then its dispatch
    steps = [(s, e) for n, s, e in ctx.program_spans if n == "train.step"]
    for n, s, e in ctx.program_spans:
        assert any(lo <= s and e <= hi for lo, hi in steps), n
