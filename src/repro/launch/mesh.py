"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before the first
jax initialization.

Production target: TPU v5e pods, 256 chips each (16x16), 2 pods = 512 chips
for the multi-pod dry-run. Axes: 'pod' (cross-pod DP), 'data' (DP + FSDP),
'model' (TP + EP + seq-sharded decode).
"""

from __future__ import annotations

import jax

# TPU v5e hardware constants (roofline terms — see EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
HBM_PER_CHIP = 16 * 1024**3   # 16 GiB


def auto_axes(n: int) -> tuple:
    """``axis_types`` for an ``n``-axis mesh whose shardings the compiler
    propagates (the model code only constrains activations)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, auto_axes(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"), auto_axes(2))
