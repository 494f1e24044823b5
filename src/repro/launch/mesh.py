"""Production mesh factory.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query.

Target hardware: TPU v5e pods, 256 chips each (16x16 ICI torus);
multi-pod = 2 pods / 512 chips over DCN.
"""
from __future__ import annotations

import jax

# v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~3 links usable per axis)


def make_production_mesh(*, multi_pod: bool = False, layout: str = "16x16"):
    """layout: '16x16' (mandated production mesh) or an alternative
    (data, model) factorization of the same 256-chip pod — e.g. '32x8' for
    expert-parallel MoE (§Perf B4: the model axis must divide num_experts
    for EP to engage)."""
    if multi_pod:
        shape, axes = (2, 16, 16), ("pod", "data", "model")
    else:
        d, m = (int(x) for x in layout.split("x"))
        assert d * m == 256, f"layout {layout} is not a 256-chip pod"
        shape, axes = (d, m), ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_host_mesh():
    """1-device mesh for CPU smoke runs of the same sharded code paths."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))
