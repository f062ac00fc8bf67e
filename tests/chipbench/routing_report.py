"""A stand-in for the program's own report of its expert choices.

The harness judges a routed cell under the routing that the train step
reports in ``metrics["routing"]``: ``experts`` (int32) and ``kept``
(bool), each ``(moe_layers, tokens, top_k)`` in the batch's token order.
The program's step does not report it yet, and the benchmark may not
change the program, so these tests give the compiled step that report
from outside: ``moe._positions_within_expert`` is wrapped so that each
call sends the experts it ranks, and their ranks, to the host through an
ordered ``jax.debug.callback``, and the compiled step is wrapped so that
its metrics carry the choices of its forward pass.  ``kept`` is a rank
under the capacity of ``moe_ffn``, ``max(1, int(capacity_factor * N * k
/ E))`` over the ``N`` tokens of one call.

One device only.  Each microbatch calls once a layer in the forward pass
and, where the layer is recomputed for the backward pass, once more a
layer in reverse order; the forward pass's calls are kept, and
``recomputed_differs`` counts the choices that the recomputation made
otherwise (none on the CPU; on a TPU v5e at Granite widths some, PERF.md
section 2).  Delete this file once the step reports ``routing`` itself.
"""
from __future__ import annotations

import numpy as np


class RoutingReport:
    def __init__(self, cfg):
        self.cfg = cfg
        self.calls = []
        self.recomputed_differs = 0

    def install(self, monkeypatch) -> "RoutingReport":
        """Wrap the program's rank of choices within their expert, before
        the step is traced."""
        import jax
        from repro.models import moe

        ranks = moe._positions_within_expert

        def record(flat_e, n_experts):
            rank = ranks(flat_e, n_experts)
            jax.debug.callback(
                lambda e, r: self.calls.append((np.asarray(e),
                                                np.asarray(r))),
                flat_e, rank, ordered=True)
            return rank

        monkeypatch.setattr(moe, "_positions_within_expert", record)
        return self

    def routing(self) -> dict:
        """The choices of the step that ran last, in the batch's order."""
        import jax

        jax.effects_barrier()
        calls, self.calls = self.calls, []
        cfg = self.cfg
        micro, layers, k = (cfg.parallel.microbatches, cfg.n_layers,
                            cfg.top_k)
        per = len(calls) // micro
        if per not in (layers, 2 * layers) or per * micro != len(calls):
            raise RuntimeError(f"{len(calls)} routing calls for {micro} "
                               f"microbatches of {layers} layers")
        experts, kept = [], []
        for b in range(micro):
            block = calls[b * per:(b + 1) * per]
            fwd = block[:layers]
            if per == 2 * layers:
                self.recomputed_differs += sum(
                    int(np.sum(f[0] != r[0]))
                    for f, r in zip(fwd, block[:layers - 1:-1]))
            e = np.stack([c[0] for c in fwd]).reshape(layers, -1, k)
            rank = np.stack([c[1] for c in fwd]).reshape(layers, -1, k)
            n = e.shape[1]
            cap = max(1, int(cfg.capacity_factor * n * k / cfg.n_experts))
            experts.append(e.astype(np.int32))
            kept.append(rank < cap)
        return {"experts": np.concatenate(experts, axis=1),
                "kept": np.concatenate(kept, axis=1)}

    def wrap(self, compiled):
        return _Reporting(compiled, self)


class _Reporting:
    """The compiled step, its metrics with ``routing`` added."""

    def __init__(self, compiled, report: RoutingReport):
        self._compiled, self._report = compiled, report

    def __call__(self, *args):
        params, opt_state, step, metrics = self._compiled(*args)
        return params, opt_state, step, dict(
            metrics, routing=self._report.routing())

    def __getattr__(self, name):
        return getattr(self._compiled, name)


def install_in_program(monkeypatch, program_cls) -> list:
    """Give every ``Program`` built after this call the report; returns
    the list that collects each program's ``RoutingReport``."""
    reports = []
    compile_ = program_cls.compile

    def compile_with_report(self, params, opt_state, batch):
        report = RoutingReport(self.cfg).install(monkeypatch)
        reports.append(report)
        compile_(self, params, opt_state, batch)
        self.compiled = report.wrap(self.compiled)
        return self.compiled

    monkeypatch.setattr(program_cls, "compile", compile_with_report)
    return reports
