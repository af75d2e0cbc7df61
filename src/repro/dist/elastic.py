"""Elastic re-meshing: shrink the mesh after device loss and keep going.

Policy: after losing chips, rebuild an ``(data, model)`` mesh over the
survivors.  The model axis wants to stay a power of two (TP collectives
degrade badly on odd rings) and no wider than 16 (one ICI torus edge),
so ``choose_mesh_shape`` gives the model axis the largest power-of-two
divisor of the survivor count up to 16 and hands the rest to data.
Checkpoints are layout-free (host numpy), so restore-with-shardings onto
the new mesh is the whole recovery story -- see ``Checkpointer.restore``.
"""
from __future__ import annotations

import jax

from repro.launch.mesh import make_mesh

_MAX_MODEL = 16


def choose_mesh_shape(n_devices: int) -> tuple[int, int]:
    """``n_devices`` -> (data, model); always satisfies
    ``data * model == n_devices``."""
    if n_devices <= 0:
        raise ValueError(f"need at least one device, got {n_devices}")
    model = 1
    while model * 2 <= _MAX_MODEL and n_devices % (model * 2) == 0:
        model *= 2
    return n_devices // model, model


def remesh(n_devices: int | None = None, *, tp_pref: int | None = None,
           devices=None):
    """Build a fresh ``(data, model)`` mesh over the surviving devices.

    ``devices`` is the survivor list (e.g. ``healthy`` filtered through
    the fault monitor); without it the prefix of ``jax.devices()`` is
    used, which is only correct when the *tail* of the fleet died.
    ``tp_pref`` pins the model-axis width when it divides the survivor
    count (keep TP degree stable across a shrink when possible);
    otherwise falls back to ``choose_mesh_shape``.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(
            f"asked for {n_devices} devices, only {len(devices)} alive")
    if tp_pref and n_devices % tp_pref == 0:
        shape = (n_devices // tp_pref, tp_pref)
    else:
        shape = choose_mesh_shape(n_devices)
    return make_mesh(shape, ("data", "model"), devices=devices[:n_devices])
