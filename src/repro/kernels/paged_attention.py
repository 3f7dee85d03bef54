"""Paged decode attention over the log-structured KV slab pool.

This is the serving-side consumer of the paper's technique: sequences write
KV blocks append-only into slabs; the MDC cleaner relocates live blocks and
rewrites the block tables; this kernel reads through those tables.

Pool layout: (layers, num_pages, Kh, page_T, D) — a page holds each kv
head's (page_T, D) tile contiguously, so one grid step's K (or V) block is a
whole (page_T, D) tile: the (8, 128) block rule of the Mosaic lowering holds
for page_T a multiple of 8 (16 for bf16, whose native tile is (16, 128)), and
the pool carries no tile padding.  The layer index rides in scalar prefetch
too, so the decode step hands the kernel the whole stacked pool and no
per-layer slice of it is ever copied.

Tiling: grid (B, Kh, n_pages); the block table, sequence lengths and layer
index ride in scalar-prefetch SMEM (`PrefetchScalarGridSpec`) so each grid
step's k/v page fetch address is known *before* the step runs — the Pallas
pipeline can then overlap the HBM→VMEM page pull with the previous page's
compute, exactly the "overlap compaction/compute" property DESIGN.md §2
calls for.

Per grid step the VMEM working set is one (T, D) K page + one V page + the
(G, D) query group + (G, D) accumulator ≈ 2·T·D·2B + small — for T=64,
D=128: ~33 KiB.  Pages beyond a sequence's length are skipped via pl.when
(no compute, though the page fetch itself is pipelined regardless).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from ..distributed.sharding import shard_map_unchecked

NEG_INF = float("-inf")


def _pa_kernel(block_tables_ref, seq_lens_ref, layer_ref,  # scalar prefetch
               q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               page_T: int, n_pages: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = seq_lens_ref[b]
    valid_here = seq_len - j * page_T  # tokens of this page that are live

    @pl.when(valid_here > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0, 0].astype(jnp.float32)         # (T, D)
        v = v_ref[0, 0, 0].astype(jnp.float32)         # (T, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, T)
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(col < valid_here, logits, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(m_new == NEG_INF, 0.0, jnp.exp(logits - m_new))
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_bkgd(q, k_pools, v_pools, block_tables, seq_lens, layer,
                         *, interpret: bool | None = None):
    """q: (B, Kh, G, D); k_pools/v_pools: (L, num_pages, Kh, T, D);
    block_tables: (B, P) int32 (clamped to valid page ids by the caller);
    seq_lens: (B,) int32; layer: int32 scalar, the pool layer to read.
    Returns (B, Kh, G, D).  ``interpret=None`` auto-selects: Mosaic on TPU,
    interpret mode everywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Kh, G, D = q.shape
    T = k_pools.shape[3]
    P = block_tables.shape[1]
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    kernel = functools.partial(_pa_kernel, page_T=T, n_pages=P,
                               scale=1.0 / (D ** 0.5))

    def kv_map(b, kh, j, bt, sl, li):
        return (li[0], bt[b, j], kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Kh, P),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, kh, j, bt, sl, li: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, 1, T, D), kv_map),
            pl.BlockSpec((1, 1, 1, T, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, kh, j, bt, sl, li: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, D), q.dtype),
        interpret=interpret, name="paged_attention",
    )(block_tables, seq_lens, layer, q, k_pools, v_pools)


def paged_attention_sharded(q, k_pools, v_pools, block_tables, seq_lens,
                            layer, *, mesh, axis: str = "model",
                            interpret: bool | None = None):
    """Tensor-parallel paged attention: one independent kernel per shard over
    its local kv heads (grid (B, Kh/n, P)), zero cross-device traffic.

    GSPMD cannot partition a ``pallas_call`` custom call, so the mesh path is
    an explicit ``shard_map`` along the head axis.  q: (B, Kh, G, D) and the
    pools shard their kv-head dim over ``axis``; the block tables, sequence
    lengths and layer index are *replicated* — the host computes one
    placement / compaction plan and every shard reads KV through the same
    physical page ids (DESIGN.md §6).  Each head's online softmax runs
    unchanged on its owning shard, so outputs are bitwise identical to the unsharded kernel.
    """
    head_spec = PartitionSpec(None, axis, None, None)   # (B, Kh, G, D)
    # (L, pages, Kh, T, D)
    pool_spec = PartitionSpec(None, None, axis, None, None)
    rep = PartitionSpec()
    fn = functools.partial(paged_attention_bkgd, interpret=interpret)
    return shard_map_unchecked(
        fn, mesh,
        in_specs=(head_spec, pool_spec, pool_spec, rep, rep, rep),
        out_specs=head_spec,
    )(q, k_pools, v_pools, block_tables, seq_lens, layer)
