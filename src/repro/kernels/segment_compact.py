"""Segment compaction — the paper's cleaning data path on TPU.

"Read the segment, re-write its still-live pages" (paper §2) becomes, on a
TPU HBM pool, a block-table-driven gather: for each destination slot of a
fresh slab, pull the payload of one live source block.  The source plan is
produced by the MDC victim selection (repro.serving.kvcache) and rides in
scalar-prefetch SMEM, so the pipeline prefetches source block i+1's payload
while block i is being written — the copy runs at HBM bandwidth, which is
exactly the cost model the paper's Wamp metric prices (each moved byte is an
HBM read + write stolen from decode).

Grid: (M destination blocks, payload row tiles).  A block's payload (N, E)
is viewed as (N, E/128, 128) lane rows, and each grid step copies a
(rows, 128) window of one block: the Mosaic lowering wants the last two
block dims divisible by (8, 128) or equal to the array's, which a (1, E)
row of an (N, E) array is not.  The window bounds the VMEM working set
regardless of block payload size.  For a paged K/V pool whose pages are
(Kh, T, D) with D = 128 and T a multiple of the dtype's sublane tile, the
view is a bitcast of the pool's HBM layout — no relayout copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _compact_kernel(src_ref, pool_ref, out_ref):
    del src_ref  # only used by the index maps
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def segment_compact(pool, src_idx, *, tile: int = 8192,
                    interpret: bool | None = None):
    """pool: (N, E) block payloads; src_idx: (M,) int32.

    Returns (M, E) == pool[src_idx], as a pipelined HBM gather-copy.
    E is padded to a lane multiple (128) if needed; ``tile`` elements bound
    one grid step's window.  ``interpret=None`` auto-selects: Mosaic on TPU,
    interpret mode everywhere else.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N, E = pool.shape
    (M,) = src_idx.shape
    pad = (-E) % _LANES
    if pad:
        pool = jnp.pad(pool, ((0, 0), (0, pad)))
    R = (E + pad) // _LANES
    # rows per window: a multiple of 32 (the sublane tile of every dtype
    # down to 8 bits) that divides R, else the whole block
    r = min(R, max(tile // _LANES, 1))
    if R % r or r % 32:
        r = R
    rows = pool.reshape(N, R, _LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M, R // r),
        in_specs=[pl.BlockSpec((1, r, _LANES),
                               lambda i, e, src: (src[i], e, 0))],
        out_specs=pl.BlockSpec((1, r, _LANES), lambda i, e, src: (i, e, 0)),
    )
    out = pl.pallas_call(
        _compact_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, R, _LANES), pool.dtype),
        interpret=interpret, name="segment_compact",
    )(src_idx, rows)
    return out.reshape(M, R * _LANES)[:, :E]
