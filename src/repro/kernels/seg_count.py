"""Pallas TPU kernel: segment boundaries over sorted signatures.

After sorting the (N, 2) uint32 signatures produced by ``sig_hash``, the
group-by reduces to marking rows that differ from their predecessor.  AMI
(Def. 4.7) is the sum of the boundary vector; per-segment lengths give the
class multiplicities (Def. 4.5).

The kernel is a blocked elementwise compare between the signature block and
the one-row-shifted block (the wrapper materializes the shift, so no
cross-block halo exchange is needed).  The segment count is the boundary
vector summed in XLA.

Layout: the wrapper turns the ``(C, N, 2)`` row-major signatures into
``(C, 2, N)`` hi/lo planes, so rows run along the 128-wide lane axis and a
``(1, 2, TILE_N)`` block is two lane-dense rows (a ``(TILE_N, 2)`` block
would fill 2 of 128 lanes).  The boundary output is ``(C, 1, N)`` with
``(1, 1, TILE_N)`` blocks: its last two block dimensions are the full
unit dimension and a multiple of 128, which the TPU lowering accepts at
any C and N.  A 2-D ``(N, 2)`` input is the C = 1 case of the same launch;
the candidate axis of one ``sweep_candidates`` lowering is the leading
grid dimension, and the first-row-always-differs shift is materialized per
candidate, so every candidate keeps its own segment count (and its own
padded-sentinel segment, which the caller subtracts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 8192
LANES = 128


def _seg_kernel(cur_ref, prev_ref, bound_ref):
    # blocks are (1, 2, tile): the hi and lo planes of one candidate's tile
    cur = cur_ref[0]
    prev = prev_ref[0]
    diff = (cur[0:1] != prev[0:1]) | (cur[1:2] != prev[1:2])
    bound_ref[0] = diff.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def seg_boundaries(sig_sorted: jax.Array, interpret: bool = True
                   ) -> tuple[jax.Array, jax.Array]:
    """(N, 2) sorted sigs -> ((N,) int32 boundaries, () int32 n_segments).

    A ``(C, N, 2)`` stack (each candidate sorted along its own row axis)
    maps to ``((C, N) boundaries, (C,) counts)`` in one launch.
    """
    if sig_sorted.ndim == 2:
        bounds, counts = seg_boundaries(sig_sorted[None], interpret=interpret)
        return bounds[0], counts[0]
    c, n, _ = sig_sorted.shape
    # equality is sign-agnostic: compare the bits as int32 planes
    cur = jnp.swapaxes(jax.lax.bitcast_convert_type(sig_sorted, jnp.int32),
                       1, 2)                                   # (c, 2, n)
    # prev[i] = sig[i-1]; row 0 compares against ~sig[0] so it always differs
    prev = jnp.concatenate([~cur[:, :, :1], cur[:, :, :-1]], axis=2)
    tile = min(TILE_N, -(-n // LANES) * LANES)
    n_pad = -n % tile
    # pad prev with the same values as cur so padded rows never count
    cur = jnp.pad(cur, ((0, 0), (0, 0), (0, n_pad)))
    prev = jnp.pad(prev, ((0, 0), (0, 0), (0, n_pad)))
    spec = pl.BlockSpec((1, 2, tile), lambda ci, i: (ci, 0, i))
    bounds = pl.pallas_call(
        _seg_kernel,
        grid=(c, (n + n_pad) // tile),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, 1, tile), lambda ci, i: (ci, 0, i)),
        out_shape=jax.ShapeDtypeStruct((c, 1, n + n_pad), jnp.int32),
        interpret=interpret,
    )(cur, prev)[:, 0, :n]
    return bounds, bounds.sum(axis=1)
