"""Readings that the limits of a cell are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 12 --control-seeds 3 [--out results/calib.json]

For each of ``--seeds`` seeds: the program's first steps, exactly as a
run takes them, against the reference (the lower readings).  For each of
``--control-seeds`` seeds: the control (the reference with every matmul
in float8 e4m3) and the fault of half the batch left out, planted in the
reference put in the program's place, each against the reference (the
upper readings).  A state left unchanged reads 1 on ``change_gap`` and
needs no run.  Every reading is judged by ``check.judge`` against the
workload file's limits: each program line has to say ``correct true``,
each control and fault line ``correct false``.  Not part of a benchmark
run.

Where the cell routes tokens to experts, the reference applies the
routing that the program, or the control in its place, reports; the
lines also carry ``route_gap``, ``kept_gap``, ``route_margin`` and
``dropped_share``, and two more faults run on the control seeds: the
program's routing with the first kept expert of each token swapped for
the one the reference ranks last (``fault.route``), and the program's
experts with the capacity counted in reverse token order
(``fault.drop``).
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NUMBERS = ("loss_gap", "grad_gap", "grad_median_gap", "change_gap")
ROUTE_NUMBERS = ("route_gap", "kept_gap", "route_margin", "dropped_share")


def calibrate(prog, wl: dict, conf: dict, traf: dict, seeds,
              control_seeds: int, diag=()) -> dict:
    """The readings of ``seeds`` (program) and of the first
    ``control_seeds`` of them (control, faults, ``diag`` witnesses) on
    the program ``prog`` (a ``cell.Program``)."""
    from benchmarks.chip import cell, check, traffic

    n = wl["check_steps"]
    out = {"workload": wl["name"], "limits": wl["limits"], "program": [],
           "control": [], "faults": {}}

    def record(kind, seed, got, want):
        g = check.gaps(got, want)
        ok, _ = check.judge(g, wl["limits"])
        row = {"seed": seed, "correct": ok,
               **{k: g[k] for k in NUMBERS + ROUTE_NUMBERS if k in g},
               "grad_leaf": g["grad_leaf"], "change_leaf": g["change_leaf"]}
        print(kind, json.dumps(row), flush=True)
        return row

    groups = cell.token_groups(wl)
    refs = {}
    ref = check.Reference(conf, wl["optimizer"], groups=groups)
    for i, seed in enumerate(seeds):
        pool = traffic.batch_pool(traf, conf["vocab_size"], wl["chips"],
                                  seed)
        params, opt_state = prog.init_state(seed)
        if prog.compiled is None:
            prog.compile(params, opt_state, prog.place(pool[0]))
        t = time.perf_counter()
        params, opt_state, got = prog.first_steps(params, opt_state, pool,
                                                  seed, n)
        t_prog = time.perf_counter() - t
        cell._free(params, opt_state)
        t = time.perf_counter()
        check.require_routing(conf, wl["limits"], got)
        want = ref.run(seed, pool[:n], routing=got.get("routing"))
        t_ref = time.perf_counter() - t
        print(f"seed {seed} program s {t_prog:.2f} reference s {t_ref:.2f}",
              flush=True)
        if i < control_seeds:
            refs[seed] = (pool, got, want)
        out["program"].append(record("program", seed, got, want))
    prog.compiled = None

    def against_ref(seed, pool, got, want):
        """``want`` where ``got`` applied the program's routing, else the
        reference handed ``got``'s."""
        if not ref.routed:
            return want
        return ref.run(seed, pool[:n], routing=got["routing"])

    control = check.Reference(conf, wl["optimizer"], mode="fp8",
                              groups=groups)
    for seed, (pool, prog_got, want) in refs.items():
        routing = "own" if ref.routed else None
        got = control.run(seed, pool[:n], routing=routing)
        out["control"].append(record("control", seed, got,
                                     against_ref(seed, pool, got, want)))
        got = ref.run(seed, pool[:n], fault="half",
                      routing=prog_got.get("routing"))
        out["faults"].setdefault("half", []).append(
            record("fault.half", seed, got, want))
        for fault in ("route", "drop") if ref.routed else ():
            got = ref.run(seed, pool[:n], fault=fault,
                          routing=prog_got["routing"])
            out["faults"].setdefault(fault, []).append(
                record(f"fault.{fault}", seed, got,
                       against_ref(seed, pool, got, want)))
    for mode in diag:
        other = check.Reference(conf, wl["optimizer"], mode=mode,
                                groups=groups)
        for seed, (pool, _, want) in refs.items():
            got = other.run(seed, pool[:n],
                            routing="own" if ref.routed else None)
            out.setdefault(mode, []).append(record(
                mode, seed, got, against_ref(seed, pool, got, want)))
    summary = {}
    for k in NUMBERS + ROUTE_NUMBERS:
        if k not in out["program"][0]:
            continue
        summary[k] = {
            "lower": max(r[k] for r in out["program"]),
            **({"control_min": min(r[k] for r in out["control"])}
               if out["control"] else {}),
            **{f"{f}_min": min(r[k] for r in rs)
               for f, rs in out["faults"].items()}}
    summary["correct"] = {
        "program": [r["correct"] for r in out["program"]],
        "control": [r["correct"] for r in out["control"]],
        **{f: [r["correct"] for r in rs] for f, rs in out["faults"].items()}}
    out["summary"] = summary
    print("summary", json.dumps(summary), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--out", default=None)
    ap.add_argument("--diag", default="",
                    help="'bf16': also read the reference computed in "
                         "bfloat16 against it on the control seeds (a "
                         "witness of what bfloat16 alone does)")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import cell
    from repro.launch.train import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    wl, conf, traf = cell.load(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print("calibrate.py: needs the cell's chips", file=sys.stderr)
        return 3
    prog = cell.Program(wl, conf, traf, devices)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = calibrate(prog, wl, conf, traf, seeds, args.control_seeds,
                    [m for m in args.diag.split(",") if m])
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
