"""Production mesh construction (TPU v5e, 256 chips/pod).

Functions, not module-level constants: importing this module never touches
jax device state (the dry run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from ..compat import make_mesh


def production_axis_sizes(*, multi_pod: bool = False) -> dict[str, int]:
    """Axis-name -> size of the production mesh, as plain metadata --
    enough for core.policy.plan() to resolve a ShardingPlan without
    creating the 256/512 virtual devices (dryrun --plan-only)."""
    if multi_pod:
        return {"pod": 2, "data": 16, "model": 16}
    return {"data": 16, "model": 16}


def make_production_mesh(*, multi_pod: bool = False):
    sizes = production_axis_sizes(multi_pod=multi_pod)
    return make_mesh(tuple(sizes.values()), tuple(sizes))


def make_local_mesh(data: int = 1, model: int = 1, pod: int | None = None,
                    *, devices=None):
    """Mesh over however many (possibly host-platform) devices exist, or
    over ``devices`` (e.g. ``jax.devices()[:1]`` for a one-chip mesh on a
    four-chip host)."""
    if pod is not None:
        return make_mesh((pod, data, model), ("pod", "data", "model"),
                         devices=devices)
    return make_mesh((data, model), ("data", "model"), devices=devices)


# TPU v5e hardware constants (per chip) for the roofline model
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link
