"""One run of one cell: set-up, the first steps checked against the
reference, the measured window, and the result line.

Set-up builds the program's one step object and state
(``repro.launch.train.build``, weights made on the device from the seed,
zero AdamW state, the step compiled or loaded from the persistent
cache), draws the pool of distinct batches, and drives the first
``check_steps`` steps through ``train_loop`` over that compiled step with
the window's own feed.  Those steps are also the warm-up.  The window
then drives the same object through ``train_loop`` for ``--seconds``:
each step takes the next pool batch, placed with the runtime's batch
spec; ``on_step`` waits on the previous step's loss (a one-step lag) and
records when it completed.  After the window the program's state is
freed and the reference runs its first steps from the same seed.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import pathlib
import statistics
import sys
import time
import types

import numpy as np

from . import check, flops, hlo, peaks, traffic, weights
from . import trace as tr

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
MIN_SPAN_S = 0.25
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class _WindowClosed(Exception):
    pass


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load(name: str) -> tuple[dict, dict, dict]:
    """(workload, configuration, traffic) of the cell ``name``."""
    wl = _json(HERE / "workloads" / f"{name}.json")
    conf = _json(HERE / "configs" / f"{wl['config']}.json")
    return wl, conf, traffic.load(wl["traffic"])


def per_layer_entries(name: str) -> list[dict]:
    """The per-layer metric entries of ``BENCHMARK.json`` that the cell
    ``name`` reports."""
    bench = _json(ROOT / "BENCHMARK.json")
    return [m for m in bench["per_layer"]
            if "workloads" not in m or name in m["workloads"]]


def program_config(conf: dict, wl: dict):
    """The program's ``ModelConfig`` for the configuration file, checked
    against the file's sizes."""
    from repro.configs import get_config

    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["registry"]),
                              **prog.get("replace", {}))
    par = dataclasses.replace(cfg.parallel, microbatches=wl["microbatches"],
                              param_store=wl["store"])
    cfg = dataclasses.replace(cfg, parallel=par,
                              optimizer=wl["optimizer"]["name"])
    want = {"d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "hd": conf["assumed"]["head_dim"],
            "d_ff": conf["intermediate_size"],
            "vocab": conf["vocab_size"],
            "n_layers": conf["num_hidden_layers"],
            "rope_theta": conf["rope_theta"],
            "norm_eps": conf["rms_norm_eps"],
            "tie_embeddings": conf["tie_word_embeddings"],
            "mlp": {"silu": "swiglu"}[conf["hidden_act"]],
            "qkv_bias": conf["assumed"]["qkv_bias"],
            "n_experts": conf.get("num_local_experts", 0),
            "sliding_window": None, "attn_softcap": None,
            "final_softcap": None, "post_norms": False}
    if want["n_experts"]:
        want.update(top_k=conf["num_experts_per_tok"],
                    capacity_factor=conf["capacity_factor"],
                    moe_aux_coef=conf["router_aux_loss_coef"])
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{conf['name']}: {got} != {want}")
    return cfg


def token_groups(wl: dict) -> int:
    """Groups of tokens that the step routes apart: one a chip and
    microbatch."""
    return wl["chips"] * wl["microbatches"]


def _process_age() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        ticks = int(open("/proc/self/stat").read().rsplit(")", 1)[1]
                    .split()[19])
        uptime = float(open("/proc/uptime").read().split()[0])
        import os
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Program:
    """The system under test at one cell's size: runtime, compiled step
    and the functions that read its first steps."""

    def __init__(self, wl: dict, conf: dict, traf: dict, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_local_mesh
        from repro.launch.train import build

        self.wl, self.conf, self.traf = wl, conf, traf
        self.chips = wl["chips"]
        mesh_shape = wl["mesh"]
        self.cfg = program_config(conf, wl)
        self.mesh = make_local_mesh(mesh_shape["data"], mesh_shape["model"],
                                    devices=devices[:self.chips])
        self.runtime, self.optimizer = build(self.cfg, self.mesh)
        self.shapes = check.reference_module(conf).Model(conf).shapes()
        weights.check_layout(self.runtime, self.shapes)
        self.make_params = weights.program_params_fn(self.runtime,
                                                     self.shapes)
        state = self.optimizer.state_shapes(self.runtime)
        self.make_opt = jax.jit(
            lambda: jax.tree.map(lambda s: jax.numpy.zeros(s.shape, s.dtype),
                                 state),
            out_shardings=jax.tree.map(lambda s: s.sharding, state))
        self.grad_norms = weights.program_grad_norms_fn(
            self.runtime, wl["optimizer"]["b1"])
        self.change_norms = weights.program_change_norms_fn(self.runtime,
                                                            self.shapes)
        self.scalar = NamedSharding(self.mesh, P())
        self.step_fn = self.runtime.make_train_step(self.optimizer)
        self.compiled = None
        self.batch_sharding = None

    def batch_spec(self, example):
        from jax.sharding import NamedSharding

        if self.batch_sharding is None:
            spec = self.runtime.batch_pspec({"tokens": example})["tokens"]
            self.batch_sharding = NamedSharding(self.mesh, spec)
        return self.batch_sharding

    def place(self, host_tokens):
        import jax

        return {"tokens": jax.device_put(host_tokens,
                                         self.batch_spec(host_tokens))}

    def init_state(self, seed: int):
        return self.make_params(seed), self.make_opt()

    def compile(self, params, opt_state, batch):
        import jax
        import jax.numpy as jnp

        step = jax.device_put(jnp.int32(0), self.scalar)
        self.compiled = self.step_fn.lower(params, opt_state, step,
                                           batch).compile()
        return self.compiled

    def first_steps(self, params, opt_state, pool, seed: int, steps: int):
        """Steps ``0 .. steps-1`` through ``train_loop`` on pool batches
        ``0 .. steps-1``; returns (params, opt_state, readings).  Where the
        step reports its expert choices (``metrics["routing"]``), the
        readings keep a host copy of each step's (``routing``)."""
        from repro.launch.train import train_loop

        losses, grad, change, routing = [], [None], [None], []
        key = weights.base_key(seed)

        def on_step(i, params, opt_state, metrics):
            losses.append(metrics["loss"])
            if "routing" in metrics:
                routing.append({k: np.asarray(metrics["routing"][k])
                                for k in ("experts", "kept")})
            if i == 0:
                grad[0] = self.grad_norms(opt_state["m"])
            if i == steps - 1:
                change[0] = self.change_norms(params, key)

        params, opt_state = train_loop(
            self.runtime, self.compiled, params, opt_state,
            lambda i: self.place(pool[i % len(pool)]), steps,
            on_step=on_step)
        import jax
        readings = {"loss": [float(x) for x in losses],
                    "grad": jax.tree.map(np.asarray, grad[0]),
                    "change": jax.tree.map(np.asarray, change[0])}
        if routing:
            readings["routing"] = routing
        return params, opt_state, readings

    def hbm_plan_bytes(self) -> int:
        m = self.compiled.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _free(*trees) -> None:
    import jax

    for t in trees:
        for x in jax.tree.leaves(t):
            if hasattr(x, "delete") and not x.is_deleted():
                x.delete()
    gc.collect()


def window(prog: Program, params, opt_state, pool, start: int,
           seconds: float):
    """Drive ``train_loop`` over the compiled step for ``seconds``.
    Returns (completion times with the window's start first, failed
    steps, compilations, final state)."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.launch.train import train_loop

    compiles = [0]

    def listen(event, *_, **__):
        if event in COMPILE_EVENTS:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    pending, times, failed, last = [], [], [0], [None]

    def batch_for(i):
        with TraceAnnotation("bench.place"):
            return prog.place(pool[i % len(pool)])

    def step(*args):
        with TraceAnnotation("bench.dispatch"):
            return prog.compiled(*args)

    def wait(loss):
        with TraceAnnotation("bench.wait"):
            value = float(loss)
        times.append(time.perf_counter())
        if not math.isfinite(value):
            failed[0] += 1

    t0 = time.perf_counter()

    def on_step(i, params, opt_state, metrics):
        pending.append(metrics["loss"])
        last[0] = (params, opt_state)
        if len(pending) >= 2:
            wait(pending[-2])
            if times[-1] - t0 >= seconds:
                raise _WindowClosed

    try:
        with TraceAnnotation("bench.window"):
            try:
                params, opt_state = train_loop(
                    prog.runtime, step, params, opt_state, batch_for,
                    start + 10 ** 9, start=start, on_step=on_step)
            except _WindowClosed:
                params, opt_state = last[0]
            wait(pending[-1])
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return [t0] + times, failed[0], compiles[0], (params, opt_state)


def step_ms_p90(times) -> float:
    """90th percentile of the time per step, each sample taken on the
    host's clock over consecutive steps that together span at least
    ``MIN_SPAN_S`` (the clock is good to about half a millisecond)."""
    dts = np.diff(times)
    k = max(1, math.ceil(MIN_SPAN_S / float(np.median(dts))))
    n = len(dts) // k
    per_step = dts[:n * k].reshape(n, k).mean(axis=1) * 1e3 if n else \
        np.asarray([dts.mean() * 1e3])
    if len(per_step) < 2:
        return float(per_step.max())
    return float(statistics.quantiles(per_step, n=10)[-1])


def e2e_metrics(times, tokens_per_step: int, setup_s: float,
                hbm_bytes: int) -> dict:
    steps = len(times) - 1
    return {"tokens_per_s": {"value": steps * tokens_per_step
                             / (times[-1] - times[0]), "unit": "tokens/s"},
            "step_ms_p90": {"value": step_ms_p90(times), "unit": "ms"},
            "hbm_peak_gb": {"value": hbm_bytes / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}


def _load_metric(name: str):
    import importlib.util

    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"{__package__}.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reduce_trace(prog: Program, trace_dir: str, steps: int,
                 per_layer: list[dict], peak: dict, tokens_per_step: int):
    """(per-layer metrics, busy_s, window_s, breakdown) of a traced
    window."""
    import glob

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no trace written under {trace_dir}")
    dev_ids = {d.id for d in prog.mesh.devices.flat}
    trace = tr.load_xplane(paths[-1], dev_ids)
    win = trace.window()
    text = prog.compiled.as_text()
    kernels = hlo.custom_calls(text)
    coll_names = hlo.collective_op_names(text)
    labels = hlo.op_labels(text)
    ctx = types.SimpleNamespace(
        trace=trace, window=win, window_s=win[1] - win[0], steps=steps,
        chips=prog.chips, peak=peak, kernels=kernels,
        collective=lambda n: n in coll_names,
        collectives=hlo.collective_bytes(text),
        flops_per_step=flops.train_flops_per_token(
            prog.conf, prog.traf["seq"]) * tokens_per_step)
    metrics = {}
    for m in per_layer:
        value = _load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    busy = tr.busy(trace, win)
    busy_s = float(np.mean([busy.get(d, 0.0) for d in dev_ids]))
    ops = [(labels.get(n, n), e - s) for ev in trace.ops.values()
           for n, s, e in tr.clip_named(ev, *win)]
    n_dev = max(1, len(trace.ops))
    first = min(dev_ids)
    breakdown = {
        "device_ops": [[n, s / n_dev] for n, s in tr.top(ops)],
        "idle_gaps": tr.top((n, g) for n, g, _, _ in
                            tr.idle_gaps(trace, win, first))}
    return metrics, busy_s, win[1] - win[0], breakdown


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, wl: dict | None = None,
        conf: dict | None = None, traf: dict | None = None,
        per_layer: list[dict] | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    import jax

    if wl is None:
        wl, conf, traf = load(name)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r}, "
                     f"{dev.device_kind})")
    if len(devices) < wl["chips"]:
        raise NoChip(f"cell {name} needs {wl['chips']} chips, JAX found "
                     f"{len(devices)}")
    peak = peaks.peaks(dev.device_kind) if require_tpu else None

    from repro.launch.train import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device platform {dev.platform} kind {dev.device_kind} count "
        f"{wl['chips']} compile cache {cache}")

    prog = Program(wl, conf, traf, devices)
    pool = traffic.batch_pool(traf, conf["vocab_size"], wl["chips"], seed)
    tokens_per_step = pool.shape[1] * pool.shape[2]
    params, opt_state = prog.init_state(seed)
    t = time.perf_counter()
    prog.compile(params, opt_state, prog.place(pool[0]))
    log(f"compile or cache load s {time.perf_counter() - t!r}")
    hbm_bytes = prog.hbm_plan_bytes()
    n_check = wl["check_steps"]
    params, opt_state, got = prog.first_steps(params, opt_state, pool, seed,
                                              n_check)
    check.require_routing(conf, wl["limits"], got)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s!r}")

    trace_dir = None
    if trace:
        import jax.profiler

        trace_dir = str(ROOT / "results" / "chipbench" / f"{name}.{seed}")
        seconds = min(seconds, wl["trace_seconds"])
        jax.profiler.start_trace(trace_dir)
    try:
        times, failed, compiles, state = window(prog, params, opt_state,
                                                pool, n_check, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    del params, opt_state
    steps = len(times) - 1
    used = devices[:wl["chips"]]
    stats = [d.memory_stats() or {} for d in used]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    log(f"peak_bytes_in_use {peak_bytes} (beside hbm_peak_gb "
        f"{hbm_bytes / 1e9!r} from the compiled plan)")
    log(f"window steps {steps} s {times[-1] - times[0]!r} compilations "
        f"{compiles}")
    if compiles:
        raise RuntimeError(f"{compiles} compilations inside the window")

    result = {"correct": False, "attempted": n_check + steps,
              "failed": failed}
    if trace:
        if per_layer is None:
            per_layer = per_layer_entries(name)
        metrics, busy_s, window_s, breakdown = reduce_trace(
            prog, trace_dir, steps, per_layer, peak, tokens_per_step)
    else:
        metrics = e2e_metrics(times, tokens_per_step, setup_s, hbm_bytes)
    _free(state)
    prog.compiled = None
    gc.collect()

    ref = check.Reference(conf, wl["optimizer"], groups=token_groups(wl))
    t = time.perf_counter()
    want = ref.run(seed, pool[:n_check], routing=got.get("routing"))
    log(f"reference s {time.perf_counter() - t!r}")
    g = check.gaps(got, want)
    ok, checks = check.judge(g, wl["limits"])
    log(f"worst leaves: grad {g['grad_leaf']} change {g['change_leaf']}; "
        f"left out of the change: {g['left_out']}")
    log(f"losses program {got['loss']} reference {want['loss']}")
    if failed:
        checks["failed_steps"] = {"value": failed, "limit": 0}
    result["correct"] = bool(ok and not failed)
    result["metrics"] = metrics
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": wl["chips"], "memory_peak_bytes": peak_bytes}
    if trace:
        result["device"].update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = breakdown
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result
