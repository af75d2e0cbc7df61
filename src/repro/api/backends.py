"""Execution backends: *where* subset evaluations run.

The paper's algorithms are backend-agnostic -- every step of E.FSP / G.FSP
reduces to "evaluate ``#Edges(SP', C, G)`` for candidate subsets SP'".
A backend owns the execution substrate behind two methods:

* ``evaluate(store, class_id, props, n_s, am)`` -- one candidate subset
  (Def. 4.8 objective), exact host arithmetic.
* ``workspace(store, class_id, props, n_s, am)`` -- a per-(class, descent)
  :class:`repro.core.sweep.SweepWorkspace`: the object matrix is
  extracted through the ``GraphIndex`` joins ONCE, device backends upload
  it ONCE, and every candidate batch -- a greedy drop-one sweep or a
  whole E.FSP lattice level fed to ``sweep_candidates`` -- is served
  from that parent buffer (host backends slice it; device backends mask
  columns on device inside a shape-bucketed jitted sweep that compiles
  once per power-of-two ``(n_b, k_b, c_b)`` bucket and dispatches ONE
  lowering per batch, sharded backends one ``shard_map`` collective
  schedule per batch).

The greedy loop itself (``GreedyDetector``) charges the SAME evaluation
count for the same sweep on every backend -- ``len(SP)`` when the sweep
runs, 0 when the children would be sub-star (``|SP'| < 2``) -- so
``FSPResult.evaluations`` is backend-invariant.

Three implementations are registered by name:

==========  =================================================================
``host``    the paper's sequential numpy loop (reference semantics)
``device``  one batched jax lowering per sweep (vmapped signature group-by,
            Pallas kernels when available), bucket-cached across classes
``sharded`` the bucketed sweep with rows sharded over the mesh's
            data-parallel axes, layout routed through
            ``repro.dist.sharding.make_plan``
==========  =================================================================
"""
from __future__ import annotations

import types
from typing import Protocol, Sequence, runtime_checkable

from repro.core.star import StarSweepResult, evaluate_subset
from repro.core.sweep import (DeviceSweepWorkspace, HostSweepWorkspace,
                              ShardedSweepWorkspace, SweepWorkspace)
from repro.core.triples import TripleStore

from repro.registry import Registry


@runtime_checkable
class ExecutionBackend(Protocol):
    """Strategy protocol: where candidate-subset evaluations execute."""

    name: str

    def evaluate(self, store: TripleStore, class_id: int,
                 props: Sequence[int], n_s: int, am: int) -> StarSweepResult:
        ...

    def workspace(self, store: TripleStore, class_id: int,
                  props: Sequence[int], n_s: int, am: int) -> SweepWorkspace:
        ...


class HostBackend:
    """The paper-faithful sequential numpy path."""

    name = "host"

    def evaluate(self, store, class_id, props, n_s, am):
        return evaluate_subset(store, class_id, props, n_s, am)

    def workspace(self, store, class_id, props, n_s, am):
        return HostSweepWorkspace(store, class_id, props, n_s, am)


class DeviceBackend:
    """Batched jax sweep: all |SP| candidates in one bucketed lowering."""

    name = "device"

    def __init__(self, use_kernel: bool = True) -> None:
        self.use_kernel = use_kernel

    def evaluate(self, store, class_id, props, n_s, am):
        # single-subset evaluation is cheaper (and exact) on host
        return evaluate_subset(store, class_id, props, n_s, am)

    def workspace(self, store, class_id, props, n_s, am):
        return DeviceSweepWorkspace(store, class_id, props, n_s, am,
                                    use_kernel=self.use_kernel)


class ShardedBackend:
    """Bucketed sweep with the object matrix row-sharded over the mesh.

    Layout policy is routed through the ``repro.dist`` planner: the mesh's
    data-parallel axes come from ``sharding.make_plan`` (DP ladder,
    tensor-parallel axis excluded), rows are bucket-padded to the DP
    degree and placed with ``PartitionSpec(dp_axes, None)``, and padding
    rows are masked out of the distinct-signature count.  With
    ``mesh=None`` this degrades to the single-device bucketed sweep
    (useful for tests, and it shares the device jit cache).

    On a real mesh the WHOLE candidate stack of a sweep runs through
    ``core.distributed.ami_bucketed_batch`` -- the explicit shard_map
    (hash-bucket all_to_all + psum) path with a leading candidate axis,
    one lowering per descent.  The collective schedule is explicit
    because the implicit GSPMD lowering of the sort-based sweep was
    seen to miscount distinct rows on multi-axis meshes (per-shard
    segment counts summed across replicas).
    """

    name = "sharded"

    def __init__(self, mesh=None, cfg=None, use_kernel: bool = True) -> None:
        self.mesh = mesh
        self.use_kernel = use_kernel
        self.plan = None
        if mesh is not None:
            from repro.dist import sharding as dsh
            # tp=True reserves the "model" axis: FSP rows shard over the
            # data-parallel axes only (matching the seed gfsp_distributed)
            cfg = cfg if cfg is not None else types.SimpleNamespace(
                tp=True, fsdp=False, seq_shard=False)
            self.plan = dsh.make_plan(cfg, mesh)

    def evaluate(self, store, class_id, props, n_s, am):
        return evaluate_subset(store, class_id, props, n_s, am)

    def workspace(self, store, class_id, props, n_s, am):
        return ShardedSweepWorkspace(store, class_id, props, n_s, am,
                                     mesh=self.mesh, plan=self.plan,
                                     use_kernel=self.use_kernel)


BACKENDS = Registry("execution backend")
BACKENDS.register("host", HostBackend)
BACKENDS.register("device", DeviceBackend)
BACKENDS.register("sharded", ShardedBackend)


def register_backend(name: str, cls) -> None:
    BACKENDS.register(name, cls)


def get_backend(spec, **opts) -> ExecutionBackend:
    """Resolve a backend: a registered name (instantiated with ``opts``)
    or an already-constructed backend instance (returned as-is)."""
    if isinstance(spec, str):
        return BACKENDS.get(spec)(**opts)
    if isinstance(spec, ExecutionBackend):
        return spec
    raise TypeError(f"not an execution backend: {spec!r}")
