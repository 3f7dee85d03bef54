"""Paged decode attention over the log-structured KV slab pool.

This is the serving-side consumer of the paper's technique: sequences write
KV blocks append-only into slabs; the MDC cleaner relocates live blocks and
rewrites the block tables; this kernel reads through those tables.

Pool layout: (layers, num_pages, Kh, page_T, D) — a page holds every kv
head's (page_T, D) tile contiguously, so one DMA of ``pool[layer, page]``
fetches the page for all heads.  The pools stay in HBM (``pl.ANY``); the
layer index, block table and sequence lengths ride in scalar-prefetch SMEM,
so the decode step hands the kernel the whole stacked pool and no per-layer
slice of it is ever copied.

Tiling: grid (B,), one step per sequence covering all of its kv heads.  A
step walks its sequence in blocks of :func:`pages_per_block` pages (about
512 tokens, fixed by the static shapes), and visits only live blocks: the
trip count is ``ceil(seq_len / block tokens)``, and within the last block
the pages past the live length are not fetched (:func:`pages_fetched`).
K and V pages are DMA'd by hand into a double buffer: while block ``i``
computes, block ``i + 1`` is in flight, and the last block of a sequence
puts the next sequence's first block in flight, so DMA latency is paid once
per call rather than once per sequence.  The buffer slot and the "first
block already in flight" flag carry across grid steps in SMEM scratch,
which is why the grid dimension is sequential (``"arbitrary"``).

Rows past ``seq_len`` are masked in the logits and, by a ``where`` on the
rows, in V: an unfetched page of the buffer holds a stale block (or, in
interpret mode, uninitialized memory), and ``0 * NaN`` would be NaN.
q.k feeds the pool's dtype straight to the MXU with f32 accumulation; the
online softmax (m, l, acc) and p.v stay in f32.

VMEM: the K and V double buffers are 2 x 2 x pages_per_block x Kh x T x D
elements — at page_T 16, Kh 8, D 128 in bf16, 32 pages of 32 KiB each per
buffer: 4 MiB in all — plus one head's f32 V block (256 KiB) and the
(Kh, G, D) f32 accumulator.
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
BLOCK_TOKENS = 512   # tokens of K (and of V) one block fetches


def pages_per_block(page_T: int, n_pages: int) -> int:
    """Pages one block of the kernel covers: about ``BLOCK_TOKENS`` tokens,
    at most the table width ``n_pages``."""
    return min(n_pages, max(1, BLOCK_TOKENS // page_T))


def pages_fetched(seq_lens, page_T: int):
    """Pages the kernel fetches for a sequence of ``seq_lens`` tokens (at
    most the table's): its live pages.  Plain integer arithmetic, so the
    kernel and the engine's page counter share it."""
    return (seq_lens + page_T - 1) // page_T


def _pa_kernel(bt_ref, lens_ref, layer_ref,          # scalar prefetch (SMEM)
               q_ref, k_hbm, v_hbm,                  # q block; pools in HBM
               o_ref,
               k_buf, v_buf, sems, m_ref, l_ref, acc_ref, state_ref, *,
               batch: int, page_T: int, n_pages: int, ppb: int,
               scale: float):
    b = pl.program_id(0)
    Kh = q_ref.shape[1]
    bk = ppb * page_T
    layer = layer_ref[0]

    def length(s):              # attended tokens of sequence s, table-capped
        return jnp.minimum(lens_ref[s], n_pages * page_T)

    def n_blocks(s):
        return pl.cdiv(pages_fetched(length(s), page_T), ppb)

    def copy_block(s, i, slot, start: bool):
        """Start (or wait for) the DMAs of block ``i`` of sequence ``s`` into
        buffer ``slot``: one per live page for K and one for V."""
        first = i * ppb
        live = jnp.minimum(pages_fetched(length(s), page_T) - first, ppb)

        def page(p, carry):
            pid = bt_ref[s * n_pages + first + p]
            for hbm, buf, kv in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                c = pltpu.make_async_copy(hbm.at[layer, pid], buf.at[slot, p],
                                          sems.at[kv, slot])
                if start:
                    c.start()
                else:
                    c.wait()
            return carry

        jax.lax.fori_loop(0, live, page, 0)

    @pl.when(b == 0)
    def _reset():
        state_ref[0] = 0        # buffer slot of the block about to compute
        state_ref[1] = 0        # 1: this sequence's first block is in flight

    nb = n_blocks(b)
    nxt = jnp.minimum(b + 1, batch - 1)
    next_seq_live = (b + 1 < batch) & (n_blocks(nxt) > 0)

    @pl.when((nb > 0) & (state_ref[1] == 0))
    def _first_fetch():
        copy_block(b, 0, state_ref[0], True)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(i, carry):
        slot = state_ref[0]
        more = i + 1 < nb

        # the next block, of this sequence or the next, goes in flight first
        @pl.when(more)
        def _prefetch_own():
            copy_block(b, i + 1, 1 - slot, True)

        @pl.when(jnp.logical_not(more) & next_seq_live)
        def _prefetch_next_seq():
            copy_block(nxt, 0, 1 - slot, True)

        copy_block(b, i, slot, False)
        valid = length(b) - i * bk      # live rows of this block, > 0
        for h in range(Kh):
            q = q_ref[0, h]                                    # (G, D)
            k = k_buf[slot, :, h].reshape(bk, -1)              # (bk, D)
            ct = jnp.promote_types(q.dtype, k.dtype)
            logits = jax.lax.dot_general(
                q.astype(ct), k.astype(ct), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (G, bk)
            col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(col < valid, logits, NEG_INF)
            v = v_buf[slot, :, h].reshape(bk, -1).astype(jnp.float32)
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < valid, v, 0.0)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
            alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
            p = jnp.where(m_new == NEG_INF, 0.0, jnp.exp(logits - m_new))
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        state_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, nb, body, 0)
    state_ref[1] = ((nb > 0) & next_seq_live).astype(jnp.int32)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_bkgd(q, k_pools, v_pools, block_tables, seq_lens, layer,
                         *, interpret: bool | None = None):
    """q: (B, Kh, G, D); k_pools/v_pools: (L, num_pages, Kh, T, D);
    block_tables: (B, P) int32 (clamped to valid page ids by the caller);
    seq_lens: (B,) int32, at most P * T; layer: int32 scalar, the pool
    layer to read.  Returns (B, Kh, G, D).  ``interpret=None``
    auto-selects: Mosaic on TPU, interpret mode everywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Kh, G, D = q.shape
    T = k_pools.shape[3]
    P = block_tables.shape[1]
    ppb = pages_per_block(T, P)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    kernel = functools.partial(_pa_kernel, batch=B, page_T=T, n_pages=P,
                               ppb=ppb, scale=1.0 / (D ** 0.5))
    q_spec = pl.BlockSpec((1, Kh, G, D), lambda b, bt, sl, li: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, Kh, T, D), k_pools.dtype),
            pltpu.VMEM((2, ppb, Kh, T, D), v_pools.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (K/V, buffer slot)
            pltpu.VMEM((Kh, G, 1), jnp.float32),
            pltpu.VMEM((Kh, G, 1), jnp.float32),
            pltpu.VMEM((Kh, G, D), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_attention",
    )(block_tables.reshape(-1), seq_lens, layer, q, k_pools, v_pools)


def paged_attention_sharded(q, k_pools, v_pools, block_tables, seq_lens,
                            layer, *, mesh, axis: str = "model",
                            interpret: bool | None = None):
    """Tensor-parallel paged attention: one independent kernel per shard over
    its local kv heads (a page's Kh/n heads stay contiguous in the shard's
    pool), zero cross-device traffic.

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
