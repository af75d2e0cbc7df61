"""Controls: the program with one stated guarantee broken.

Each control is a context manager that patches the program where it
produces its answer, by the cell's driver; a run under it must come out
``correct: false``.  ``bench/control.py`` runs them on the chip at the
cells' own sizes, and ``bench/tests`` at a size a test run can hold.

* ``compact``: factorization leaves out each surrogate's ``type`` edge
  (one edge saved per molecule, the tempting step), so G' no longer
  expands to G: lossless factorization is broken.
* ``ingest``: an apply of several coalesced insert batches leaves out
  the last observation's 9 triples and still acknowledges it as applied
  (a cheaper merge): acknowledged writes are lost.

``FAULTS`` are the faults a cell's timed path can have besides, each
planted where the program does the work; ``bench/tests`` sees every one
come out not correct, and ``bench/control.py --fault`` runs one on the
chip:

* a step that returns its state unchanged: factorization that rewrites
  nothing (compact), an apply that applies no insert (ingest);
* an answer altered where it is produced: the device sweep counts one
  distinct object tuple too many for every candidate star pattern (its
  AMI, and the Def. 4.8 edges with it), so re-detection reports a
  wrong molecule count while factorization, which counts for itself,
  stays lossless (ingest).
"""
from __future__ import annotations

import contextlib
import importlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name: str, fn):
    old = getattr(obj, name)
    setattr(obj, name, fn(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def compact():
    factorize = importlib.import_module("repro.core.factorize")

    def drop_sg_type(old):
        def apply(spo, ents, sgs, props, class_id, type_id, *rest):
            rows = old(spo, ents, sgs, props, class_id, type_id, *rest)
            sg = (rows[:, 1] == type_id) & (rows[:, 2] == class_id) & \
                np.isin(rows[:, 0], sgs)
            return rows[~sg]
        return apply
    return _patched(factorize, "apply_molecule_map", drop_sg_type)


def ingest():
    from repro.api.snapshot import CompactionPlanner

    def drop_last(old):
        def apply_update(self, snapshot, rows):
            if isinstance(rows, np.ndarray) and rows.shape[0] > 9:
                rows = rows[:-9]
            return old(self, snapshot, rows)
        return apply_update
    return _patched(CompactionPlanner, "apply_update", drop_last)


def compact_unchanged():
    factorize = importlib.import_module("repro.core.factorize")
    return _patched(factorize, "apply_molecule_map",
                    lambda old: lambda spo, *rest: spo)


def ingest_unchanged():
    from repro.api.snapshot import CompactionPlanner

    def nothing(old):
        def apply_update(self, snapshot, rows):
            return old(self, snapshot, np.empty((0, 3), np.int32))
        return apply_update
    return _patched(CompactionPlanner, "apply_update", nothing)


def ami_miscounted():
    from repro.core.sweep import DeviceSweepWorkspace

    def one_more(old):
        def run_batch(self, stack, n_sp):
            edges, amis = old(self, stack, n_sp)
            return edges + (np.asarray(n_sp) + 1), amis + 1
        return run_batch
    return _patched(DeviceSweepWorkspace, "_run_batch", one_more)


CONTROLS = {"compact": compact, "ingest": ingest}
FAULTS = {"compact": {"state_unchanged": compact_unchanged},
          "ingest": {"state_unchanged": ingest_unchanged,
                     "ami_miscounted": ami_miscounted}}
