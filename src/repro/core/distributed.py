"""Distributed FSP detection on the production mesh (paper §6 future work).

The FSP inner loop is a group-by-signature + count-distinct over the
(entities x |SP|) object matrix.  On the 512-chip mesh:

* rows (entities) are sharded over the combined DP axes ("pod", "data");
* each device hashes its rows with the Pallas signature kernel
  (``kernels/sig_hash``), giving fixed-width 64-bit keys;
* AMI = number of distinct signatures = global sort + segment-boundary
  count.  The sort runs under GSPMD, which lowers it to a distributed
  sort (all-to-all exchanges) -- the TPU-idiomatic replacement for the
  paper's host hash map;
* G.FSP's per-iteration sweep over all |SP| one-property-removed subsets
  is DATA-PARALLEL across candidates (the paper iterates them
  sequentially): one vmapped lowering evaluates every candidate at once.

``gfsp_distributed`` runs the greedy descent of Algorithm 2 with this
device sweep, and is validated against the host implementation
(tests/test_distributed_fsp.py).  ``benchmarks/bench_fsp_scale.py``
lowers the sweep on the production mesh and reports its roofline terms
(the paper's own workload, deliverable g).
"""
from __future__ import annotations

import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .star import ami_device, edges_formula_device
from .triples import TripleStore


def pad_rows(objmat: np.ndarray, multiple: int) -> tuple[np.ndarray, int]:
    """Pad the row count to a multiple of the DP degree (sentinel rows)."""
    n = objmat.shape[0]
    pad = (-n) % multiple
    if pad:
        sentinel = np.full((pad, objmat.shape[1]), -1, objmat.dtype)
        objmat = np.concatenate([objmat, sentinel], axis=0)
    return objmat, n


@functools.partial(jax.jit, static_argnames=("n_s", "use_kernel"))
def sweep_drop_one(objmat, valid, am, n_s: int, use_kernel: bool = True):
    """Evaluate all |SP| one-property-removed candidate subsets at once.

    objmat: (n, k) int32 (row-sharded); valid: (n,) bool (padding mask).
    Returns (edges (k,), amis (k,)) for candidate j = SP minus property j.
    """
    n, k = objmat.shape
    keep = jnp.stack([jnp.delete(jnp.arange(k), j, assume_unique_indices=True)
                      for j in range(k)])              # (k, k-1) static
    stacked = jnp.take(objmat, keep.T, axis=1)         # (n, k-1, k)
    stacked = stacked.transpose(2, 0, 1)               # (k, n, k-1)
    amis = jax.vmap(
        lambda m: ami_device(m, valid=valid, use_kernel=use_kernel))(stacked)
    edges = edges_formula_device(amis, am, k - 1, n_s)
    return edges, amis


@functools.partial(jax.jit, static_argnames=("n_s", "n_sp", "use_kernel"))
def eval_subset_device(objmat, valid, am, n_sp: int, n_s: int,
                       use_kernel: bool = True):
    a = ami_device(objmat, valid=valid, use_kernel=use_kernel)
    return edges_formula_device(a, am, n_sp, n_s), a


def shard_rows(objmat: np.ndarray, mesh) -> jax.Array:
    """Place the object matrix row-sharded over every non-"model" axis."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    return jax.device_put(objmat, NamedSharding(mesh, P(dp, None)))


def gfsp_distributed(store: TripleStore, class_id: int, *, mesh=None,
                     use_kernel: bool = True):
    """Algorithm 2 (G.FSP) with the mesh-sharded device sweep.

    Compatibility wrapper over the unified pipeline: equivalent to
    ``repro.api.Compactor(detector="gfsp", backend="sharded",
    backend_opts={"mesh": mesh}).detect(store, class_id)``.  Control flow
    (stop criteria, tie-breaking, evaluation accounting) is the shared
    ``GreedyDetector`` loop, so host / device / sharded results are
    identical by construction (asserted in tests/test_distributed_fsp.py).
    """
    from repro.api import GreedyDetector, ShardedBackend

    backend = ShardedBackend(mesh=mesh, use_kernel=use_kernel)
    return GreedyDetector().detect(store, class_id, backend=backend)


# per-destination headroom of the hash-bucket exchange, in fair shares:
# no signature can be dropped while it is at least the row-shard count
CAP_FACTOR = 4.0


def dp_shards(mesh, dp_axes: Sequence[str]) -> int:
    """Number of row shards: the product of the mesh's ``dp_axes`` sizes."""
    n = 1
    for a, s_ in zip(mesh.axis_names, mesh.devices.shape):
        if a in dp_axes:
            n *= s_
    return n


def exchange_shape(n_rows: int, n_cands: int, n_shards: int,
                   cap_factor: float = CAP_FACTOR
                   ) -> tuple[int, int, int, int]:
    """Static shape of the uint32 buffer each shard sends in
    :func:`ami_bucketed_batch`'s ``all_to_all``: ``(n_shards, c, cap, 2)``
    for ``n_rows`` rows over ``n_shards`` shards, with a per-destination
    capacity of ``cap_factor`` times a fair share (plus 8 slots)."""
    nl = n_rows // n_shards
    cap = max(int(cap_factor * nl / n_shards) + 8, 8)
    return n_shards, n_cands, cap, 2


def ami_bucketed_batch(objmat, valid, col_masks, mesh, *, dp_axes=("data",),
                       cap_factor: float = CAP_FACTOR,
                       use_kernel: bool = True):
    """Candidate-batched distinct-row count via ONE hash-bucket exchange.

    The sort-based AMI is exact but a distributed sort exchanges the data
    over O(log^2 S) merge rounds (bench_fsp_scale baseline: 3035 s of
    collectives at D1D2D3 scale).  Here every signature moves ONCE: each
    shard routes signatures to their hash-owner with one all_to_all
    (static per-destination capacity; uniform murmur hashes make a 4x
    headroom overflow probability ~Poisson-tail negligible, and overflow
    is detected and summed so exactness violations are observable), the
    owner dedups locally, and a psum merges counts.

    The candidate axis rides the same schedule end to end: all C
    column-mask candidates hash in one batched signature launch (Pallas
    grid axis over candidates), route through ONE ``all_to_all`` whose
    buffer carries a candidate dimension, and dedup/psum as (C,) vectors
    -- one shard_map lowering per sweep instead of one per candidate.

    objmat: (n, k) int32 row-sharded over ``dp_axes``; valid: (n,) bool;
    col_masks: (C, k) int32 replicated column masks (1 = keep column).
    Returns ``(amis, dropped)``, both (C,) int32: the AMI per candidate,
    and the signatures the exchange dropped for want of capacity, which
    the AMI counts as distinct (an upper bound; exact when 0).  No shard
    can overflow while ``cap_factor >= n_shards``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops as kops

    n_shards = dp_shards(mesh, dp_axes)

    def body(mat, val, masks):
        nl = mat.shape[0]
        c = masks.shape[0]
        # candidates are column-masked views of the one sharded buffer
        mats = mat[None, :, :] * masks[:, None, :]           # (c, nl, k)
        # mask-aware signature: padding rows get the shared sentinel,
        # independently per candidate
        sig = kops.row_signature(mats, valid=val, use_kernel=use_kernel)
        sentinel = jnp.uint32(kops.SIG_SENTINEL)
        owner = (sig[..., 0] % jnp.uint32(n_shards)).astype(jnp.int32)
        owner = jnp.where(val[None, :], owner, n_shards)  # invalid -> dump
        cap = exchange_shape(nl * n_shards, c, n_shards, cap_factor)[2]
        order = jnp.argsort(owner, axis=1)
        owner_s = jnp.take_along_axis(owner, order, axis=1)
        sig_s = jnp.take_along_axis(sig, order[..., None], axis=1)
        starts = jax.vmap(
            lambda os: jnp.searchsorted(os, jnp.arange(n_shards)))(owner_s)
        pos = jnp.arange(nl)[None, :] - jnp.take_along_axis(
            starts, jnp.minimum(owner_s, n_shards - 1), axis=1)
        keep = (owner_s < n_shards) & (pos < cap)
        dropped = jnp.sum((owner_s < n_shards) & (pos >= cap), axis=1)
        # cap+1: slot ``cap`` is the dump slot for non-kept entries --
        # dumping them at (0, 0) would overwrite a real signature
        buf = jnp.full((n_shards, c, cap + 1, 2), sentinel, jnp.uint32)
        ci = jnp.broadcast_to(jnp.arange(c)[:, None], (c, nl))
        buf = buf.at[jnp.where(keep, owner_s, 0), ci,
                     jnp.where(keep, pos, cap)].set(
            jnp.where(keep[..., None], sig_s, sentinel))
        buf = buf[:, :, :cap]
        # ONE exchange for the whole stack: shard i sends slab j to shard
        # j; the candidate axis tags along inside each slab
        recv = jax.lax.all_to_all(buf, dp_axes, split_axis=0,
                                  concat_axis=0, tiled=True)
        flat = recv.transpose(1, 0, 2, 3).reshape(c, -1, 2)
        sig_sorted, _ = kops.sort_signatures(flat)     # per-candidate sort
        _, n_groups = kops.seg_boundaries(sig_sorted,
                                          use_kernel=use_kernel)   # (c,)
        has_sent = jnp.any(jnp.all(sig_sorted == sentinel, axis=-1),
                           axis=-1)                                # (c,)
        local_distinct = n_groups - has_sent.astype(jnp.int32)
        total = jax.lax.psum(local_distinct, dp_axes)
        dropped = jax.lax.psum(dropped, dp_axes)
        total = total + dropped  # upper-bound fix
        return total, dropped

    spec_m = P(dp_axes, None)
    spec_v = P(dp_axes)
    # check_vma=False: pallas_call outputs do not carry vma metadata yet
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec_m, spec_v, P(None, None)),
                         out_specs=(P(None), P(None)), check_vma=False)(
        objmat, valid, col_masks)


def ami_bucketed(objmat, valid, mesh, *, dp_axes=("data",),
                 cap_factor: float = CAP_FACTOR, use_kernel: bool = True):
    """Single-candidate distinct-row count: the C = 1 special case of
    :func:`ami_bucketed_batch` with an all-ones column mask (kept as the
    stable entry point for callers outside the sweep engine)."""
    masks = jnp.ones((1, objmat.shape[1]), jnp.int32)
    return ami_bucketed_batch(
        objmat, valid, masks, mesh, dp_axes=dp_axes,
        cap_factor=cap_factor, use_kernel=use_kernel)[0][0]
