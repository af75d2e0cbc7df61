"""Shape-bucketed, candidate-batched sweep workspaces: the detectors'
hot loop.

The seed executed every G.FSP descent step by re-extracting the class's
object matrix from the store (full-graph ``np.isin`` scans per candidate)
and -- on the jax backends -- re-tracing the drop-one sweep at a fresh
``(n, k)`` shape for every (class, candidate-size) pair.  That made the
"accelerated" paths ~2 orders of magnitude slower than the numpy loop
(BENCH_fsp.json: 3089 ms device vs 32 ms host detect).

A :class:`SweepWorkspace` fixes both costs structurally:

* **one extraction per class**: the object matrix over the *full*
  property set S is pulled through the ``GraphIndex`` joins once, at
  descent start.  Every candidate evaluation -- on every backend,
  including host -- is a column view of that parent matrix; the store is
  never touched again.  (Consequence: all backends share the same
  §4.3-(a) entity universe -- entities complete over S -- which the seed's
  host loop re-decided per subset while the device path did not.)
* **one upload per class**: the device workspaces ship the matrix to
  device once; descent steps drop columns *on device* by masking them to
  a constant, so child matrices never round-trip through the host.
* **one lowering per candidate batch**: ``sweep_candidates`` evaluates an
  ARBITRARY stack of C column-mask candidates in a single jitted call --
  the drop-one sweep is the C = |SP| special case, and E.FSP's
  breadth-first lattice scan feeds each whole subset level through it.
  On the sharded workspace the candidate axis rides one ``shard_map``
  lowering (``distributed.ami_bucketed_batch``) instead of one collective
  schedule per candidate.
* **one compile per bucket shape**: ``(n, k, c)`` pads up to a
  power-of-two bucket (rows carry a validity mask, columns a zero mask,
  padding candidates are all-zero no-ops), so the jitted sweep traces
  once per ``(n_b, k_b, c_b)`` bucket and is cache-hit for every
  subsequent class, descent level, lattice level, and ``Compactor``
  instance.  Masking a column to zero is AMI-exact: the column
  contributes the same constant to every row's signature, so the
  distinct-row count equals the count over the surviving columns.

``TRACE_COUNTS`` records one entry per traced bucket shape, and
``EXEC_STATS`` counts executed lowerings vs logical descents (one
``sweep``/``sweep_candidates`` call = one descent) -- the benchmark
snapshot asserts one lowering per warm descent on the batched paths.
``SHARD_STATS`` counts what the mesh-sharded batches exchange.
"""
from __future__ import annotations

import functools
from typing import Protocol, Sequence, runtime_checkable

import numpy as np

from repro.online.metrics import span

from .index import reset_key_stats
from .star import StarSweepResult, ami, num_edges, num_edges_batch
from .triples import TripleStore

# -- bucket ladder -----------------------------------------------------------

BUCKET_MIN_ROWS = 64    # floor: tiny classes share one compiled shape
BUCKET_MIN_COLS = 2     # star patterns need >= 2 properties
BUCKET_MIN_CANDS = 2    # candidate-axis floor (mirrors the column floor)

# one lowering evaluates at most this many candidates; larger stacks are
# chunked so the masked (c_b, n_b, k_b) intermediate stays VMEM/HBM-sane
MAX_SWEEP_CANDIDATES = 256


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def bucket_rows(n: int, multiple: int = 1) -> int:
    """Row bucket: next power of two >= max(n, floor), rounded up to the
    sharding ``multiple`` (DP degree) so shards stay equal-sized."""
    nb = max(_next_pow2(n), BUCKET_MIN_ROWS)
    if multiple > 1:
        nb += (-nb) % multiple
    return nb


def bucket_cols(k: int) -> int:
    return max(_next_pow2(k), BUCKET_MIN_COLS)


def bucket_candidates(c: int) -> int:
    """Candidate-axis bucket: next power of two, floored at 2, capped by
    chunking at ``MAX_SWEEP_CANDIDATES`` (callers slice larger stacks)."""
    return max(_next_pow2(min(c, MAX_SWEEP_CANDIDATES)), BUCKET_MIN_CANDS)


# -- jit trace / execution accounting ----------------------------------------

TRACE_COUNTS: dict[tuple, int] = {}

# executed-lowering accounting (every invocation, cache hits included):
# ``descents`` counts logical sweep calls, ``lowerings`` compiled-sweep
# dispatches -- the batched engine keeps their ratio at 1 for any
# candidate stack that fits one chunk
EXEC_STATS = {"lowerings": 0, "descents": 0}

# mesh-sharded candidate batches (``ShardedSweepWorkspace`` on a mesh):
# ``batches`` run, ``shards`` summed over them (``shards / batches`` is the
# row-shard count), ``exchange_bytes`` each shard sends in the batches'
# all_to_all (from the static buffer shape, summed over batches), and
# ``dropped`` signatures the exchange had no room for (the AMI then
# counts each as distinct: an upper bound, exact while this stays 0)
SHARD_STATS = {"batches": 0, "shards": 0, "exchange_bytes": 0, "dropped": 0}


def _note_trace(kind: str, shape: tuple) -> None:
    # executed at trace time only: jit cache hits never reach the body
    key = (kind,) + tuple(int(x) for x in shape)
    TRACE_COUNTS[key] = TRACE_COUNTS.get(key, 0) + 1


# auxiliary per-module counter resets (e.g. repro.query.batch's
# QUERY_EXEC) hook in here so one reset_trace_stats() call clears EVERY
# accounting surface -- a bench cell can never bleed counters into the
# next because a caller forgot a module-specific reset
_EXTRA_STAT_RESETS: list = []


def register_stats_reset(fn) -> None:
    """Register an extra zero-the-counters callback invoked by
    :func:`reset_trace_stats` (idempotent per function)."""
    if fn not in _EXTRA_STAT_RESETS:
        _EXTRA_STAT_RESETS.append(fn)


register_stats_reset(reset_key_stats)


def reset_trace_stats() -> None:
    TRACE_COUNTS.clear()
    EXEC_STATS["lowerings"] = 0
    EXEC_STATS["descents"] = 0
    for k in SHARD_STATS:
        SHARD_STATS[k] = 0
    for fn in _EXTRA_STAT_RESETS:
        fn()


def clear_compile_cache() -> None:
    """Drop the compiled sweep functions AND the trace counters -- gives
    tests a deterministic cold start regardless of process history."""
    _bucket_sweep_fn.cache_clear()
    _sharded_sweep_fn.cache_clear()
    reset_trace_stats()


def trace_count() -> int:
    """Total sweep traces since the last reset (cache misses only)."""
    return sum(TRACE_COUNTS.values())


def distinct_bucket_shapes() -> int:
    return len(TRACE_COUNTS)


def lowerings_per_descent() -> float:
    """Executed compiled-sweep calls per logical sweep since the last
    reset (0.0 on the host path, which lowers nothing)."""
    d = EXEC_STATS["descents"]
    return EXEC_STATS["lowerings"] / d if d else 0.0


# -- the compiled bucket sweep ----------------------------------------------

@functools.lru_cache(maxsize=None)
def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


@functools.lru_cache(maxsize=None)
def _bucket_sweep_fn(use_kernel: bool):
    """Build (once) the jitted candidate-batch sweep over a padded bucket.

    All data-dependent quantities -- ``am``, the per-candidate subset
    sizes, the total property count -- enter as traced values, so the jit
    cache is keyed ONLY by the bucket shape ``(n_b, k_b, c_b)``.
    """
    jax, jnp = _jax()
    from .star import ami_device_batch

    def sweep(objmat, valid, col_masks, am, n_sp, n_s):
        _note_trace("sweep", objmat.shape + (col_masks.shape[0],))
        masked = objmat[None, :, :] * col_masks[:, None, :]  # (c, n, k)
        amis = ami_device_batch(masked, valid=valid, use_kernel=use_kernel)
        edges = amis * (n_sp + 1) + am * (n_s - n_sp)
        return edges, amis

    return jax.jit(sweep)


@functools.lru_cache(maxsize=None)
def _sharded_sweep_fn(mesh, dp_axes: tuple, use_kernel: bool):
    """Jitted candidate-batch AMI through the explicit hash-bucket
    collective schedule (``core.distributed.ami_bucketed_batch``): the
    only distinct-count lowering that is exact on real multi-axis meshes,
    now carrying the whole candidate stack through ONE all_to_all.
    Returns ``(amis, dropped)``; its device program is
    ``jit_sharded_sweep``."""
    jax, jnp = _jax()
    from .distributed import ami_bucketed_batch

    def sharded_sweep(objmat, valid, col_masks):
        _note_trace("sharded", objmat.shape + (col_masks.shape[0],))
        return ami_bucketed_batch(objmat, valid, col_masks, mesh,
                                  dp_axes=dp_axes, use_kernel=use_kernel)

    return jax.jit(sharded_sweep)


# -- selection rule ----------------------------------------------------------

def pick_child(current: StarSweepResult, edges: np.ndarray,
               amis: np.ndarray, n_s: int, am: int
               ) -> tuple[StarSweepResult, int]:
    """Shared selection rule: first AMI == 1 candidate (paper Alg. 2
    lines 14-18), else minimum #Edges, first index breaking ties.
    Returns the child result and the dropped position ``j``."""
    single = np.where(amis == 1)[0]
    j = int(single[0]) if single.size else int(np.argmin(edges))
    child_props = tuple(p for i, p in enumerate(current.props) if i != j)
    child = StarSweepResult(props=child_props, ami=int(amis[j]), am=am,
                            n_total_props=n_s, edges=int(edges[j]))
    return child, j


# -- workspaces --------------------------------------------------------------

@runtime_checkable
class SweepWorkspace(Protocol):
    """Per-(class, descent) state: extract once, sweep many.

    ``props`` is the *current* property subset (shrinks as the descent
    drops columns); ``sweep()`` returns ``(edges, amis)`` aligned with it
    (entry ``j`` = subset with ``props[j]`` removed); ``descend(j)``
    commits the drop.  ``sweep_candidates(col_masks)`` evaluates an
    arbitrary ``(C, |S|)`` 0/1 stack of column selections over the FULL
    extracted property list -- E.FSP feeds whole lattice levels through
    it -- and returns ``(edges, amis)`` aligned with the stack.
    """

    n_s: int
    am: int

    @property
    def props(self) -> tuple[int, ...]: ...

    def evaluate_current(self) -> StarSweepResult: ...

    def sweep(self) -> tuple[np.ndarray, np.ndarray]: ...

    def sweep_candidates(self, col_masks) -> tuple[np.ndarray, np.ndarray]:
        ...

    def descend(self, j: int) -> None: ...


class _WorkspaceBase:
    """Shared extraction + bookkeeping: one index-join per descent."""

    def __init__(self, store: TripleStore, class_id: int,
                 props: Sequence[int], n_s: int, am: int) -> None:
        self.class_id = int(class_id)
        self.n_s = int(n_s)
        self.am = int(am)
        self._all_props = tuple(int(p) for p in props)
        with span("detect.extract", key=self.class_id):
            self.entities, self.matrix = store.object_matrix(
                class_id, self._all_props)
        self._active = list(range(len(self._all_props)))

    @property
    def props(self) -> tuple[int, ...]:
        return tuple(self._all_props[i] for i in self._active)

    @property
    def k(self) -> int:
        return len(self._active)

    def evaluate_current(self) -> StarSweepResult:
        # exact host arithmetic over the already-extracted parent matrix
        a = ami(self.matrix[:, self._active]) if self._active else 0
        return StarSweepResult(
            props=self.props, ami=a, am=self.am, n_total_props=self.n_s,
            edges=num_edges(a, self.am, self.k, self.n_s))

    def descend(self, j: int) -> None:
        # pure bookkeeping: device buffers are untouched (the dropped
        # column is simply masked out of every subsequent sweep)
        del self._active[j]

    def _normalize_masks(self, col_masks) -> np.ndarray:
        masks = np.asarray(col_masks)
        if masks.ndim != 2 or masks.shape[1] != len(self._all_props):
            raise ValueError(
                f"col_masks must be (C, {len(self._all_props)}), "
                f"got {masks.shape}")
        # canonicalize to 0/1: the device paths MULTIPLY by the mask, so
        # any other truthy value would silently skew ids (and parity)
        return np.ascontiguousarray((masks != 0).astype(np.int32))

    def _drop_one_stack(self, n_rows: int) -> np.ndarray:
        """(n_rows, k_all) 0/1 drop-one stack: row j = active columns
        with column j dropped (a no-op candidate when j is inactive or
        beyond ``k_all`` -- callers discard those rows)."""
        k_all = len(self._all_props)
        base = np.zeros((k_all,), np.int32)
        base[self._active] = 1
        masks = np.repeat(base[None, :], n_rows, axis=0)
        idx = np.arange(min(n_rows, k_all))
        masks[idx, idx] = 0
        return masks


class HostSweepWorkspace(_WorkspaceBase):
    """Sequential numpy evaluation over column views of the parent matrix."""

    def sweep(self) -> tuple[np.ndarray, np.ndarray]:
        # no shape bucket to keep invariant on host: only the active
        # rows of the drop-one stack are evaluated
        masks = self._drop_one_stack(len(self._all_props))
        return self.sweep_candidates(masks[np.asarray(self._active)])

    def sweep_candidates(self, col_masks) -> tuple[np.ndarray, np.ndarray]:
        masks = self._normalize_masks(col_masks)
        EXEC_STATS["descents"] += 1
        n = self.matrix.shape[0]
        amis = np.empty((masks.shape[0],), np.int64)
        for i in range(masks.shape[0]):
            cols = np.flatnonzero(masks[i])
            # zero surviving columns: every row is the same empty tuple
            amis[i] = ami(self.matrix[:, cols]) if cols.size \
                else (1 if n else 0)
        n_sp = (masks != 0).sum(axis=1)
        edges = num_edges_batch(amis, self.am, n_sp, self.n_s)
        return edges, amis


class DeviceSweepWorkspace(_WorkspaceBase):
    """Batched jax sweep over a bucket-padded on-device parent buffer.

    Upload happens once, in the constructor; each candidate batch ships
    only a ``(c_b, k_b)`` mask stack.  Already-descended columns stay in
    the buffer, permanently masked -- dropping a column is a host-side
    bookkeeping update, not a transfer.
    """

    def __init__(self, store, class_id, props, n_s, am, *,
                 use_kernel: bool = True) -> None:
        super().__init__(store, class_id, props, n_s, am)
        self.use_kernel = bool(use_kernel)
        self._dev = None            # uploaded lazily, on the first sweep
        self._valid = None

    def _placement(self, n_rows: int):
        """(row_multiple, (matrix, mask) shardings | None) -- overridden
        by the mesh-sharded workspace."""
        return 1, None

    def _ensure_uploaded(self) -> None:
        """Bucket-pad and ship the parent matrix to device ONCE, on first
        use: classes whose descent never sweeps (|SP| <= 2, or a single
        pattern at full S) stay entirely on host."""
        if self._dev is not None:
            return
        jax, jnp = _jax()
        with span("sweep.upload", key=self.class_id):
            n, k = self.matrix.shape
            row_multiple, sharding = self._placement(n)
            self.n_bucket = bucket_rows(n, row_multiple)
            self.k_bucket = bucket_cols(k)
            buf = np.zeros((self.n_bucket, self.k_bucket), np.int32)
            buf[:n, :k] = self.matrix
            valid = np.arange(self.n_bucket) < n
            if sharding is not None:
                self._dev = jax.device_put(buf, sharding[0])
                self._valid = jax.device_put(valid, sharding[1])
            else:
                self._dev = jnp.asarray(buf)
                self._valid = jnp.asarray(valid)

    def sweep(self) -> tuple[np.ndarray, np.ndarray]:
        # the drop-one stack spans FULL bucket height so the compiled
        # sweep shape is invariant across descent levels (one trace per
        # bucket, not per (bucket, |SP|) pair); no-op rows are discarded
        self._ensure_uploaded()
        edges, amis = self.sweep_candidates(
            self._drop_one_stack(self.k_bucket))
        act = np.asarray(self._active)
        return edges[act], amis[act]

    def _run_batch(self, stack: np.ndarray, n_sp: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """One lowering over a (c_b, k_b) padded stack: dispatch through
        the results' copy to the host."""
        _, jnp = _jax()
        EXEC_STATS["lowerings"] += 1
        with span("sweep.batch", key=self.class_id):
            edges, amis = _bucket_sweep_fn(self.use_kernel)(
                self._dev, self._valid, jnp.asarray(stack), self.am,
                jnp.asarray(n_sp), self.n_s)
            return np.asarray(edges), np.asarray(amis)

    def sweep_candidates(self, col_masks) -> tuple[np.ndarray, np.ndarray]:
        masks = self._normalize_masks(col_masks)
        EXEC_STATS["descents"] += 1
        self._ensure_uploaded()
        n_cand, k_all = masks.shape
        edges_out = np.empty((n_cand,), np.int64)
        amis_out = np.empty((n_cand,), np.int64)
        for lo in range(0, n_cand, MAX_SWEEP_CANDIDATES):
            chunk = masks[lo:lo + MAX_SWEEP_CANDIDATES]
            c_b = bucket_candidates(chunk.shape[0])
            stack = np.zeros((c_b, self.k_bucket), np.int32)
            stack[:chunk.shape[0], :k_all] = chunk
            n_sp = stack.sum(axis=1, dtype=np.int32)
            edges, amis = self._run_batch(stack, n_sp)
            m = chunk.shape[0]
            edges_out[lo:lo + m] = edges[:m].astype(np.int64)
            amis_out[lo:lo + m] = amis[:m].astype(np.int64)
        return edges_out, amis_out


class ShardedSweepWorkspace(DeviceSweepWorkspace):
    """Device workspace with rows sharded over the mesh's DP axes.

    With ``mesh=None`` this *is* the single-device bucketed sweep (same
    jit cache, same bucket ladder).  On a real mesh the WHOLE candidate
    stack runs through one ``ami_bucketed_batch`` collective schedule per
    chunk -- one shard_map lowering per descent, not one per candidate;
    the column-drop multiply happens inside the shard_map body with row
    sharding preserved, so the buffer still uploads exactly once per
    descent.  Each batch is counted in ``SHARD_STATS``.
    """

    def __init__(self, store, class_id, props, n_s, am, *, mesh=None,
                 plan=None, use_kernel: bool = True) -> None:
        self.mesh = mesh
        self.plan = plan
        self.dp_axes: tuple = ()
        self.n_shards = 1
        super().__init__(store, class_id, props, n_s, am,
                         use_kernel=use_kernel)

    def _placement(self, n_rows: int):
        if self.mesh is None:
            return 1, None
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist.sharding import batch_axes_for

        from .distributed import dp_shards
        if self.plan is not None:
            # prefer the planner's rung for this (padded) row count
            axes = tuple(batch_axes_for(self.plan, bucket_rows(n_rows))
                         or self.plan.dp_axes)
        else:
            axes = tuple(a for a in self.mesh.axis_names if a != "model")
        self.dp_axes = axes
        self.n_shards = dp_shards(self.mesh, axes)
        return self.n_shards, (NamedSharding(self.mesh, P(axes, None)),
                               NamedSharding(self.mesh, P(axes)))

    def _run_batch(self, stack: np.ndarray, n_sp: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        if self.mesh is None:
            return super()._run_batch(stack, n_sp)
        from .distributed import exchange_shape
        _, jnp = _jax()
        EXEC_STATS["lowerings"] += 1
        fn = _sharded_sweep_fn(self.mesh, self.dp_axes, self.use_kernel)
        with span("sweep.batch", key=self.class_id):
            amis, dropped = fn(self._dev, self._valid, jnp.asarray(stack))
            amis, dropped = np.asarray(amis), np.asarray(dropped)
        SHARD_STATS["batches"] += 1
        SHARD_STATS["shards"] += self.n_shards
        SHARD_STATS["exchange_bytes"] += 4 * int(np.prod(exchange_shape(
            self.n_bucket, stack.shape[0], self.n_shards)))
        SHARD_STATS["dropped"] += int(dropped.sum())
        edges = num_edges_batch(amis, self.am, n_sp, self.n_s)
        return edges, amis
