"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init and
then calls it.

Target hardware (roofline constants): TPU v5e-class chip.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# hardware constants used by the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link direction


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes: every sharding in this repo is
    placed by GSPMD propagation or an explicit ``shard_map``, never by
    explicit-sharding types (the ``jax.make_mesh`` default)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for CPU tests (1 real device)."""
    return make_mesh(shape, axes)
