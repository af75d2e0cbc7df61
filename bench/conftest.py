"""The benchmark's tests run on the CPU over four virtual devices, so a
cell on four chips runs there as on one host of four, over a ``(4, 1)``
mesh; a one-chip cell takes the first device.  The flag takes effect
only if JAX has not started in this process, so run ``bench/tests`` on
its own:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os

FOUR = "--xla_force_host_platform_device_count=4"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS", ""), FOUR) if f)
