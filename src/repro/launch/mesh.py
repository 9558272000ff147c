"""Production mesh definitions (functions, never module-level constants —
importing this module must not touch jax device state)."""
from __future__ import annotations

import jax

def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler from the ``shard`` annotations)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n // model) or 1
    return make_mesh((data, model), ("data", "model"))


# TPU v5e-like hardware model (per chip) for the roofline analysis
PEAK_BF16_FLOPS = 197e12     # FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (we assume 1 link per path)
