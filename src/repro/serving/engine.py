"""Paged serving engine: continuous batching over the log-structured KV pool.

The engine owns the tensor pool (per-layer K/V page arrays) and executes, on
device, the two data paths the pool manager plans on host:

  * decode      — up to ``max_decode_chunk`` tokens for every active slot per
                  dispatch, reading KV through block tables
                  (kernels.paged_attention on TPU; the vectorized ref path on
                  CPU), writing each new token's K/V into its page;
  * compaction  — the paper's cleaning: gather live pages of MDC victims
                  into fresh slabs (kernels.segment_compact) and remap the
                  block tables.

The decode loop is *device-resident* (DESIGN.md §2): block tables, sequence
lengths and last-token state live on device between dispatches, the K/V
pools are donated through every jitted path (multi-step decode, prefill
scatter, compaction move) so they are updated in place, and the host only
intervenes at pre-computed *events* — the next page-boundary crossing
(``seq_len % page_T`` wrap ⇒ a fresh block must be allocated, possibly
triggering compaction), request completion, or admission.  Each dispatch
decodes ``n = min(tokens-to-next-event, max_decode_chunk)`` tokens inside a
single ``lax.fori_loop``, so host work is O(events), not O(tokens) — the
paper's "one big I/O instead of many small ones", applied to dispatch.

Supported families: dense + moe (GQA attention).  MLA pages (deepseek) would
carry the latent cache instead (smaller pages, same policy — DESIGN.md §5);
SSM state never checkerboards, so mamba2 serves from dense state and the
pool is inapplicable (also §5).

Batch slots are fixed (``max_batch``) so the decode step compiles once;
inactive slots point at a reserved trash page and are masked out.  Per-slot
bookkeeping is vectorized numpy (no Python slot objects): ``rid``, ``lens``,
``to_gen``, ``npages``, ``tokens`` arrays plus the ``bt`` block-table matrix.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..configs.base import ModelConfig
from ..distributed.sharding import (SERVING_RULES, _is_axes, resolve_spec,
                                    tree_shardings)
from ..models import Model
from ..models import attention as att
from ..models import transformer as tfm
from ..models.layers import rmsnorm
from .. import kernels
from ..kernels.paged_attention import pages_fetched
from ..core.logstructure import FENCED, JournalLog, Placement
from ..distributed.fault import TransientFault, backoff_delay
from ..obs import DeathCalibration, MetricsLogger, span
from .kvcache import LogStructuredKVPool
from .prefix_cache import PrefixCache
from .scheduler import (DEFAULT_CLEAN_BUDGET, AdmissionShed,
                        choose_preempt_victims, clean_budget,
                        make_length_predictor, normalize_prefill_chunk,
                        retry_after_estimate)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int
    # resume state (preempted/recovered requests only): the tokens already
    # emitted and delivered.  A restart re-decodes them from the prompt —
    # bit-identically — so they are not re-delivered or re-journaled.
    out: np.ndarray | None = None
    out_n: int = 0


def _pow2(n: int) -> int:
    """Smallest power of two ≥ n (≥ 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _named(name: str, fn, **kw):
    """``functools.partial(fn, **kw)`` under ``name``: jitted, it lowers to
    the program ``jit_<name>``, the name the profiler's XLA Modules line
    shows for each of its runs."""
    p = functools.partial(fn, **kw)
    p.__name__ = name
    return p


# per-head projections shard over the serving mesh; every other weight is
# replicated (see _serving_param_shardings)
_HEAD_SHARDED_PARAMS = ("wq", "wk", "wv")


def _serving_param_shardings(model: Model, params, mesh):
    """Tensor-parallel placement of the serving params: ``wq``/``wk``/``wv``
    shard their head axis over the mesh "model" axis; everything else —
    ``wo``, MLP/MoE, norms, embeddings — replicates.

    Replicating ``wo`` (instead of Megatron's row-parallel split) is a
    deliberate serving trade: after an all-gather of the tiny per-head
    context vectors, every cross-head contraction is computed in full on
    every shard, in the same summation order as the 1-device engine — which
    is what makes sharded decode *bit-identical*, not just numerically close
    (DESIGN.md §6).  The HBM-bandwidth-dominant state (the K/V pools) and
    the attention compute still shard fully.
    """
    def mask(path, ax):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return tuple(ax) if name in _HEAD_SHARDED_PARAMS else (None,) * len(ax)

    axes = jax.tree_util.tree_map_with_path(mask, model.axes(),
                                            is_leaf=_is_axes)
    return tree_shardings(axes, params, mesh, rules=SERVING_RULES)


def _paged_attn(q, k_pools, v_pools, layer, bt, lens, use_pallas: bool,
                mesh=None):
    """Decode attention over layer ``layer`` of the stacked pools."""
    if use_pallas:
        return kernels.paged_attention(q, k_pools, v_pools, bt, lens,
                                       layer=layer, mesh=mesh)
    return kernels.ref.paged_attention_ref(q, k_pools[layer], v_pools[layer],
                                           bt, lens)


def _write_row(pools, layer, page, off, row):
    """pools[layer, page[b], :, off[b]] = row[b] for each slot b, as a
    read-modify-write of the slots' whole (Kh, T, hd) pages.  A scatter of
    bare (Kh, hd) rows would stride across the page's T axis, and XLA then
    relays out — copies — the whole pool to make that row contiguous."""
    pages = pools[layer, page]                                # (B, Kh, T, hd)
    hit = jnp.arange(pages.shape[2])[None, :] == off[:, None]  # (B, T)
    pages = jnp.where(hit[:, None, :, None], row[:, :, None, :].astype(
        pools.dtype), pages)
    return pools.at[layer, page].set(pages)


def _to_pages(x, T):
    """(L, 1, n·T, Kh, hd) token-major K/V of one sequence → its
    (L, n, Kh, T, hd) pool pages."""
    L, _, S, Kh, hd = x.shape
    return x[:, 0].reshape(L, S // T, T, Kh, hd).swapaxes(2, 3)


def _from_pages(pages):
    """(L, n, Kh, T, hd) pool pages → the (L, 1, n·T, Kh, hd) token-major
    key extent they hold."""
    L, n, Kh, T, hd = pages.shape
    return pages.swapaxes(2, 3).reshape(L, 1, n * T, Kh, hd)


def make_paged_decode_step(cfg: ModelConfig, page_T: int, use_pallas: bool,
                           max_chunk: int = 32, mesh=None, kv_shard=None,
                           rep_shard=None, stop_token: int | None = None,
                           trash_page: int | None = None):
    """Builds the jitted *multi-step* decode dispatch over the paged pool.

    The returned function has signature

        out, k_pools, v_pools, seq_lens, tokens = step(
            params, k_pools, v_pools, bt, seq_lens, tokens, active, n)

    with ``bt (B, P)`` int32 physical pages, ``seq_lens (B,)`` current
    lengths, ``tokens (B,)`` the last emitted token per slot, ``active (B,)``
    bool, and ``n`` a *traced* int32 in [1, max_chunk]: the dispatch decodes
    exactly ``n`` tokens per active slot inside one ``lax.fori_loop`` (no
    recompile when ``n`` changes) and returns them in ``out (max_chunk, B)``
    (rows ≥ n undefined).  Each iteration writes the incoming token's K/V at
    position ``seq_len`` (page ``seq_len // page_T``) and attends over
    ``seq_len + 1`` tokens.  The pools are (L, pages, Kh, page_T, hd) and
    ride in the layer scan's carry: each layer writes its K/V row in place
    and the attention kernel reads the stacked pool at that layer index, so
    no per-layer slice of the pool is copied.  Inactive slots write into
    the caller's trash page and their seq_len/token state is frozen.

    ``stop_token`` (static, None = off): after each emitted token the
    active mask drops slots whose token equals it, *inside* the fori_loop —
    a stopped slot freezes (seq_len, token, K/V writes rerouted to
    ``trash_page``) for the rest of the dispatch, so stop detection costs
    no extra host sync: the engine reads the per-slot stop positions out of
    the same once-per-dispatch token buffer.  ``trash_page`` routes frozen
    slots' dead K/V writes away from their (still real) block tables.

    K/V pools and the seq_lens/tokens state are donated: the pools are never
    copied across dispatches.

    With a serving mesh (``mesh``/``kv_shard``/``rep_shard``), the pools and
    the QKV projections arrive head-sharded; the per-head attention output is
    gathered (``rep_shard`` constraint) before the replicated ``wo``
    contraction so the epilogue — and therefore every decoded token — is
    computed bit-identically to the 1-device engine (DESIGN.md §6).
    """
    step = _build_decode_step(cfg, page_T, use_pallas, max_chunk, mesh,
                              kv_shard, rep_shard, stop_token, trash_page)
    return jax.jit(_named("decode_step", step), donate_argnums=(1, 2, 4, 5))


def _build_decode_step(cfg, page_T, use_pallas, max_chunk, mesh, kv_shard,
                       rep_shard, stop_token, trash_page):
    """The raw (unjitted) multi-step decode body — shared between the plain
    decode dispatch (make_paged_decode_step) and the fused chunked-prefill
    + decode dispatch (make_fused_prefill_decode_step), so there is exactly
    one source of truth for the decode arithmetic."""
    assert cfg.family in ("dense", "moe"), cfg.family
    assert max_chunk >= 1

    def one_token(params, k_pools, v_pools, bt, seq_lens, tokens, active):
        x = jnp.take(params["embed"], tokens[:, None], axis=0)  # (B,1,d)
        pos = seq_lens[:, None]
        page = jnp.take_along_axis(bt, (seq_lens // page_T)[:, None], 1)[:, 0]
        if trash_page is not None:
            # a slot that stopped mid-dispatch keeps its real block table;
            # route its dead writes to the trash page like any other
            # inactive slot (also keeps a slot frozen at exactly
            # npages*page_T from indexing one past its table)
            page = jnp.where(active, page, trash_page)
        off = seq_lens % page_T
        # each slot attends its seq_len + 1 tokens; on the kernel path an
        # inactive slot, whose output is discarded, attends none, so the
        # kernel fetches nothing for it (the jnp path reads every slot's
        # whole table either way)
        att_lens = seq_lens + 1
        if use_pallas:
            att_lens = jnp.where(active, att_lens, 0)

        def layer(carry, lp):
            h, kps, vps, li = carry
            hn = rmsnorm(h, lp["ln1"])
            q, k, v = att._project_qkv(hn, lp["attn"], cfg, pos)
            kps = _write_row(kps, li, page, off, k[:, 0])
            vps = _write_row(vps, li, page, off, v[:, 0])
            o = _paged_attn(q[:, 0], kps, vps, li, bt, att_lens, use_pallas,
                            mesh)
            if rep_shard is not None:
                # all-gather the (B, H, hd) context so the cross-head wo
                # contraction runs in full on every shard (bit-identity)
                o = jax.lax.with_sharding_constraint(o, rep_shard)
            h = h + jnp.einsum("bhe,hed->bd", o.astype(h.dtype),
                               lp["attn"]["wo"])[:, None]
            h = h + tfm._block_mlp(rmsnorm(h, lp["ln2"]), lp["mlp"], cfg)
            return (h, kps, vps, li + 1), None

        (x, k_pools, v_pools, _), _ = jax.lax.scan(
            layer, (x, k_pools, v_pools, jnp.int32(0)), params["blocks"])
        logits = tfm._unembed(params, x, cfg)[:, 0]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return jnp.where(active, nxt, tokens), k_pools, v_pools

    def step(params, k_pools, v_pools, bt, seq_lens, tokens, active, n):
        B = tokens.shape[0]
        out = jnp.zeros((max_chunk, B), jnp.int32)

        def body(t, carry):
            k_pools, v_pools, seq_lens, tokens, active, out = carry
            tokens, k_pools, v_pools = one_token(
                params, k_pools, v_pools, bt, seq_lens, tokens, active)
            out = jax.lax.dynamic_update_index_in_dim(out, tokens, t, 0)
            seq_lens = seq_lens + active.astype(jnp.int32)
            if stop_token is not None:
                active = active & (tokens != stop_token)
            return (k_pools, v_pools, seq_lens, tokens, active, out)

        k_pools, v_pools, seq_lens, tokens, active, out = jax.lax.fori_loop(
            0, n, body, (k_pools, v_pools, seq_lens, tokens, active, out))
        if kv_shard is not None:
            # pin the donated pools' output sharding to their input sharding
            # so the in-place buffer reuse survives under the mesh
            k_pools = jax.lax.with_sharding_constraint(k_pools, kv_shard)
            v_pools = jax.lax.with_sharding_constraint(v_pools, kv_shard)
        return out, k_pools, v_pools, seq_lens, tokens

    return step


def make_fused_prefill_decode_step(cfg: ModelConfig, page_T: int,
                                   use_pallas: bool, max_chunk: int = 32,
                                   mesh=None,
                                   kv_shard=None, rep_shard=None,
                                   stop_token: int | None = None,
                                   trash_page: int | None = None):
    """One fused dispatch = one prefill chunk + ``n`` decode tokens
    (DESIGN.md §9: chunked prefill co-scheduled with decode).

    The returned function has signature

        out, first, k_pools, v_pools, seq_lens, tokens = fused(
            params, k_pools, v_pools, bt, seq_lens, tokens, active, n,
            pf_pages, pf_chunk_pages, pf_toks, pf_pos, pf_last, kv_len=...)

    and runs, in one jitted executable over the *donated* pools:

      1. the prefill chunk — gather the prefilling slot's full key extent
         from ``pf_pages`` (its block-table row, trash-padded to
         ``ceil(kv_len / page_T)`` entries), run ``tfm.prefill_chunk`` on
         the chunk's tokens ``pf_toks`` (1, C) at traced position
         ``pf_pos``,
         scatter the fresh chunk K/V into ``pf_chunk_pages`` (the chunk's
         own pages, trash-padded), and read the ``pf_last`` row's argmax
         (``first`` — the request's first output token, meaningful on the
         final chunk);
      2. the unchanged multi-token decode ``fori_loop`` for every
         decode-active slot (the prefilling slot is masked out of
         ``active`` by the engine until its final chunk lands).

    The two halves are independent by construction — the prefilling slot's
    pages are disjoint from every decode write, and its extent gather reads
    the pre-decode pool — so fusing them costs no ordering constraint; it
    removes the monolithic prefill's full-dispatch decode stall.

    ``kv_len`` (static) is the prompt's pow2 token bucket, the same compile
    key the monolithic prefill buckets by — one fused executable per prompt
    bucket, reused by every chunk index (``pf_pos``/``pf_last`` are
    traced)."""
    decode = _build_decode_step(cfg, page_T, use_pallas, max_chunk, mesh,
                                kv_shard, rep_shard, stop_token, trash_page)

    def fused(params, k_pools, v_pools, bt, seq_lens, tokens, active, n,
              pf_pages, pf_chunk_pages, pf_toks, pf_pos, pf_last, kv_len):
        # gather the extent BEFORE scattering the chunk: the current chunk
        # attends its own unrounded K/V (spliced in at pf_pos inside
        # gqa_prefill_chunk), not the pool-dtype round trip
        ext_k = _from_pages(k_pools[:, pf_pages])[:, :, :kv_len]
        ext_v = _from_pages(v_pools[:, pf_pages])[:, :, :kv_len]
        logits, ks, vs = tfm.prefill_chunk(params, pf_toks, cfg, ext_k,
                                           ext_v, pf_pos, pf_last,
                                           gather_heads=True)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        T = k_pools.shape[3]
        k_pools = k_pools.at[:, pf_chunk_pages].set(
            _to_pages(ks, T).astype(k_pools.dtype))
        v_pools = v_pools.at[:, pf_chunk_pages].set(
            _to_pages(vs, T).astype(v_pools.dtype))
        out, k_pools, v_pools, seq_lens, tokens = decode(
            params, k_pools, v_pools, bt, seq_lens, tokens, active, n)
        return out, first, k_pools, v_pools, seq_lens, tokens

    return jax.jit(_named("fused_step", fused), donate_argnums=(1, 2, 4, 5),
                   static_argnames=("kv_len",))


def _scatter_prefill_fn(k_pools, v_pools, ks, vs, pages, shard=None):
    """Write one sequence's prefill K/V (L, 1, n·T, Kh, hd) into the pool
    pages ``pages`` (n,) (donated — no pool copy)."""
    T = k_pools.shape[3]
    k_pools = k_pools.at[:, pages].set(_to_pages(ks, T).astype(k_pools.dtype))
    v_pools = v_pools.at[:, pages].set(_to_pages(vs, T).astype(v_pools.dtype))
    if shard is not None:
        k_pools = jax.lax.with_sharding_constraint(k_pools, shard)
        v_pools = jax.lax.with_sharding_constraint(v_pools, shard)
    return k_pools, v_pools


def _move_pages_fn(k_pools, v_pools, src, dst, *, use_pallas, shard=None):
    """Compaction data path: pool[dst] = pool[src] (donated pools).

    The gather reads the pre-scatter pool, so src/dst overlap (survivors
    re-placed into a just-freed victim slab) is safe.  Under a mesh the move
    is a pure page-axis gather/scatter — every shard relocates its own head
    slice of the pages with zero cross-device traffic — so the jnp path is
    used (GSPMD partitions it); the Pallas kernel stays the 1-device fast
    path (a pallas_call is opaque to GSPMD and the flattened payload layout
    would mix the sharded head dim into the lane dim).
    """
    if use_pallas and shard is None:
        L, n_pages, *page = k_pools.shape
        kf = k_pools.reshape(L * n_pages, -1)
        vf = v_pools.reshape(L * n_pages, -1)
        off = jnp.arange(L, dtype=jnp.int32)[:, None] * n_pages
        src_l = (off + src[None, :]).reshape(-1)
        moved_k = kernels.segment_compact(kf, src_l).reshape(
            L, len(src), *page)
        moved_v = kernels.segment_compact(vf, src_l).reshape(
            L, len(src), *page)
    else:
        moved_k = k_pools[:, src]
        moved_v = v_pools[:, src]
    k_pools = k_pools.at[:, dst].set(moved_k)
    v_pools = v_pools.at[:, dst].set(moved_v)
    if shard is not None:
        k_pools = jax.lax.with_sharding_constraint(k_pools, shard)
        v_pools = jax.lax.with_sharding_constraint(v_pools, shard)
    return k_pools, v_pools


class PagedServingEngine:
    """Continuous-batching engine on the log-structured KV pool.

    ``mesh`` (a 1-D ``jax.sharding.Mesh`` with a "model" axis, e.g.
    ``launch.mesh.make_serving_mesh(8)``) turns the engine tensor-parallel:
    the K/V pools and QKV projections shard their head axis across the mesh,
    block tables / lengths / token buffers replicate, and the donation chain
    (decode → prefill scatter → compaction move) holds per shard.  The
    host-side pool manager is mesh-oblivious — one placement/compaction plan
    drives every shard — so Wamp and compaction counts are shard-invariant
    and the decoded tokens are bit-identical to the 1-device engine
    (DESIGN.md §6).  Head counts that don't divide the mesh fall back to
    replication (the resolver's divisibility rule) instead of failing.
    """

    def __init__(self, model: Model, *, n_slabs: int = 16,
                 blocks_per_slab: int = 8, page_T: int = 16,
                 max_batch: int = 4, max_seq: int = 512,
                 policy: str = "mdc", use_pallas: bool | None = None,
                 params=None, seed: int = 0,
                 compact_trigger: int = 2, compact_batch: int = 4,
                 n_open: int | None = None, streams: int | None = None,
                 demote_survivors: bool = False, max_decode_chunk: int = 32,
                 warmup: bool = False, mesh=None,
                 prefix_cache: bool = False, prefix_cache_pages: int = 0,
                 pool_dtype=jnp.bfloat16, stop_token: int | None = None,
                 preemption: bool = False, predictor: str = "ewma",
                 prefill_chunk: int = 0, admit_every_dispatch: bool = True,
                 journal_dir: str | None = None, snapshot_every: int = 0,
                 audit_every: int = 0, injector=None, fault_retries: int = 2,
                 fault_backoff_s: float = 0.0, shed_queue_depth: int = 0,
                 journal_fsync: bool = False, clock=None, tracer=None,
                 metrics_every: int = 0, metrics_sink=None,
                 calibration: bool = False, phase_log: bool = False,
                 async_compaction: bool = False, clean_budget: int = 0):
        cfg = model.cfg
        self.model, self.cfg = model, cfg
        self.page_T = page_T
        self.max_batch = max_batch
        self.max_pages_per_seq = (max_seq + page_T - 1) // page_T
        if use_pallas is None:  # backend-aware default: Mosaic on TPU only
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = use_pallas
        self.max_decode_chunk = max_decode_chunk
        # --- chunked prefill co-scheduled with decode (DESIGN.md §9) ------
        # prefill_chunk > 0: prompts prefill ``prefill_chunk`` tokens per
        # dispatch inside the *fused* prefill+decode step instead of one
        # monolithic dispatch, so running decodes never stall behind a long
        # prompt.  0 (default) keeps the monolithic prefill.
        # admit_every_dispatch: with work waiting under stop-token decode
        # (where a slot's exit is invisible to the event horizon), shrink
        # dispatches to per-token scheduling so a queued arrival never
        # sits out the rest of a dispatch behind an already-exited slot.
        self.prefill_chunk = normalize_prefill_chunk(prefill_chunk, page_T)
        self.admit_every_dispatch = admit_every_dispatch
        # Pool payload dtype.  Reuse note (DESIGN.md §7): with a reduced
        # dtype, a prefix-hit tail prefill attends the *rounded* prefix K/V
        # where a cold full prefill attends full-precision activations, so
        # hits are approximate; pool_dtype=float32 makes them bit-exact.
        self.pool_dtype = pool_dtype
        # Mosaic tiles a page's (page_T, hd) minor dims by (sublanes, 128)
        # with 8 * 4 / itemsize sublanes; a page_T off that tile is padded
        # up to it, which wastes the difference of the pool's HBM
        tile = 32 // jnp.dtype(pool_dtype).itemsize
        if (use_pallas and jax.default_backend() == "tpu"
                and page_T % tile):
            raise ValueError(f"page_T={page_T} is not a multiple of the "
                             f"{jnp.dtype(pool_dtype).name} sublane tile "
                             f"({tile}): the Mosaic pool would be padded")

        # death-stream placement (DESIGN.md §11): ``streams`` open slabs
        # routed by est-death quantiles; survivor demotion opt-in (KV
        # deaths are absolute clocks); ``n_open`` kept as the legacy alias.
        self.pool = LogStructuredKVPool(
            n_slabs, blocks_per_slab, policy=policy, streams=streams,
            n_open=n_open, demote_survivors=demote_survivors,
            compact_trigger=compact_trigger, compact_batch=compact_batch)
        self.streams = self.pool.n_open
        # synchronous plan execution: tensor move + block-table remap happen
        # before any compaction-freed page id can be re-allocated
        self.pool.on_compaction = self._execute_plan
        # --- async, budgeted compaction (DESIGN.md §13) -------------------
        # planned / in-flight / committed pipeline: the per-step pump plans
        # fenced sub-plans ahead of pressure, issues their move dispatches
        # double-buffered against decode, and applies the LUT remap at the
        # next step's sync point.  The synchronous callback above stays
        # registered as the pressure fallback (the pool drains the pipeline
        # first via on_drain, then cleans synchronously if still short).
        self.async_compaction = bool(async_compaction)
        self.clean_budget = (int(clean_budget) if clean_budget > 0
                             else DEFAULT_CLEAN_BUDGET)
        self._inflight_plans: list = []  # moves issued, remap pending
        if self.async_compaction:
            self.pool.on_drain = self._drain_compaction
            # alloc-path trigger crossings fence-plan at this grain instead
            # of compacting synchronously; the pump issues the moves
            self.pool.plan_budget = self.clean_budget
        # shared-prefix KV reuse: full-page prompt prefixes keyed in a radix
        # tree over the pool's physical pages (refcounted; DESIGN.md §7)
        self.prefix_cache = (
            PrefixCache(self.pool, page_T, capacity_pages=prefix_cache_pages)
            if (prefix_cache or prefix_cache_pages) else None)
        self._prefill_tokens_total = 0   # prompt tokens submitted to prefill
        self._prefill_tokens_saved = 0   # of those, served from the cache
        n_pages = n_slabs * blocks_per_slab
        self.trash_page = n_pages  # reserved scratch page for inactive slots

        L, Kh, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        shape = (L, n_pages + 1, Kh, page_T, hd)

        self.mesh = mesh
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError("serving mesh needs a 'model' axis; use "
                                 "launch.mesh.make_serving_mesh")
            self._rep_shard = NamedSharding(mesh, PartitionSpec())
            self._kv_shard = NamedSharding(
                mesh, resolve_spec(shape, tfm.kv_pool_axes(), mesh,
                                   SERVING_RULES))
        else:
            self._rep_shard = self._kv_shard = None
        # whether the pools actually shard (divisible kv heads) or fell back
        # to replication — the mesh-aware kernel/constraint paths key off this
        self._pool_sharded = (self._kv_shard is not None and
                              any(p is not None for p in self._kv_shard.spec))

        self.k_pools = self._zeros_kv(shape)
        self.v_pools = self._zeros_kv(shape)

        self.params = params if params is not None else model.init(
            jax.random.PRNGKey(seed))
        if mesh is not None:
            self.params = jax.device_put(
                self.params, _serving_param_shardings(model, self.params,
                                                      mesh))

        # --- host slot state: flat numpy arrays, one row per batch slot ---
        B, P = max_batch, self.max_pages_per_seq
        self.rid = np.full(B, -1, np.int64)       # owning request (-1 free)
        self.lens = np.zeros(B, np.int32)         # current sequence length
        self.to_gen = np.zeros(B, np.int32)       # tokens left to emit
        self.npages = np.zeros(B, np.int32)       # allocated pages per slot
        self.tokens = np.zeros(B, np.int32)       # last emitted token
        self.bt = np.full((B, P), self.trash_page, np.int32)
        self._out = [None] * B                    # per-slot output buffers
        self._out_n = np.zeros(B, np.int32)
        # resumed slots re-decode their already-emitted span (bit-identical
        # replay); _jskip[i] = how many output tokens were already journaled
        # and delivered, so the replayed span is not re-recorded
        self._jskip = np.zeros(B, np.int32)
        # chunked-prefill slot state: the (single) in-flight prefill.  A
        # prefilling slot owns its rid/pages/prompt like a decoding one —
        # so preemption and release go through the same decref paths — but
        # is masked out of the decode active set until its final chunk.
        self._prefilling = np.zeros(B, bool)
        self._pf: dict | None = None
        # rid -> wall-clock of first admission (TTFT queue-wait split)
        self.admit_wall: dict[int, float] = {}

        # --- device-resident mirrors (uploaded only when an event dirties
        # them; the decode dispatch itself keeps seq_lens/tokens on device) --
        self._bt_dev = self._put_rep(self.bt)
        self._lens_dev = self._put_rep(self.lens)
        self._lens_on_dev = self.lens.copy()   # _lens_dev's values, on the host
        self._tok_dev = self._put_rep(self.tokens)
        self._act_dev = self._put_rep((self.rid >= 0) & ~self._prefilling)
        self._bt_dirty = False
        self._state_dirty = False

        self.queue: collections.deque[Request] = collections.deque()
        self.finished: dict[int, list[int]] = {}
        self._admit_done: list[int] = []  # finished during admission
        # --- pressure-aware scheduling (DESIGN.md §8) ---------------------
        # stop_token: requests finish when they emit it, so output length —
        # and every page's est_death — becomes a *prediction* (the length
        # predictor, default EWMA over recent completions) instead of the
        # exact max_new_tokens.  preemption: when admission stalls and
        # compaction + prefix-cache eviction cannot cover the page deficit,
        # victim sequences are preempted (pages freed via the decref path)
        # and requeued: the resume re-prefills the prompt and re-decodes
        # the emitted span, reproducing the lost K/V bit-identically.
        self.stop_token = stop_token
        self.preemption = preemption
        self.length_predictor = make_length_predictor(predictor)
        self._resume: collections.deque[Request] = collections.deque()
        self._prompt: list[np.ndarray | None] = [None] * B
        self.preemptions = 0
        self.resumes = 0
        self.recomputed_tokens = 0  # tokens recomputed (prefill+re-decode)
        self.prefill_chunks_dispatched = 0  # fused prefill+decode dispatches
        # pass the mesh / pool sharding to the jitted paths only when the
        # pools actually shard; with replicated fallback pools everything
        # runs the plain (pallas-capable) kernels identically on every device
        move_shard = self._kv_shard if self._pool_sharded else None
        self._decode = make_paged_decode_step(
            cfg, page_T, use_pallas, max_chunk=max_decode_chunk,
            mesh=mesh if self._pool_sharded else None,
            kv_shard=self._kv_shard, rep_shard=self._rep_shard,
            stop_token=stop_token, trash_page=self.trash_page)
        self._fused = None
        if self.prefill_chunk:
            self._fused = make_fused_prefill_decode_step(
                cfg, page_T, use_pallas, max_chunk=max_decode_chunk,
                mesh=mesh if self._pool_sharded else None,
                kv_shard=self._kv_shard, rep_shard=self._rep_shard,
                stop_token=stop_token, trash_page=self.trash_page)
        # prefill K/V leave the model at the pool dtype: with an f32 pool
        # the cached prefix is the *unrounded* activation value, which is
        # what makes prefix-hit tail prefills bit-exact (DESIGN.md §7)
        self._prefill = jax.jit(
            _named("prefill", _prefill_fn, cfg=cfg, cache_dtype=pool_dtype),
            static_argnames=("max_len",))
        self._prefill_cont = jax.jit(
            _named("prefill_cont", _prefill_cont_fn, cfg=cfg,
                   cache_dtype=pool_dtype),
            static_argnames=("max_len", "kv_len"))
        self._scatter = jax.jit(
            _named("scatter_prefill", _scatter_prefill_fn,
                   shard=self._kv_shard),
            donate_argnums=(0, 1))
        self._move = jax.jit(
            _named("move_pages", _move_pages_fn, shard=move_shard),
            donate_argnums=(0, 1), static_argnames=("use_pallas",))
        self._next_rid = 0
        # --- crash safety & chaos (DESIGN.md §10) -------------------------
        # journal: one small durable record per state transition, so a kill
        # at any record boundary recovers to bit-identical output tokens
        # (pool_dtype=float32) via snapshot + bounded replay + re-prefill.
        self.journal = (JournalLog(journal_dir, fsync=journal_fsync)
                        if journal_dir else None)
        self.snapshot_every = snapshot_every
        self.audit_every = audit_every
        self.injector = injector
        self.fault_retries = fault_retries
        self.fault_backoff_s = fault_backoff_s
        # shed_queue_depth > 0: when admission has stalled past preemption
        # and the queue is this deep, submit() raises AdmissionShed with a
        # retry-after hint instead of growing head-of-line latency
        self.shed_queue_depth = shed_queue_depth
        self.shed_count = 0
        self.fault_retries_done = 0   # transient faults cleared by retry
        self.fault_unwinds = 0        # admissions unwound by a fault
        self.dispatches = 0
        # the paged-attention kernel's engagement, over every slot of every
        # decode iteration: pages it fetched, and the B x P table it could
        self.attn_pages_read = 0
        self.attn_pages_table = 0
        self._admit_stalled = False
        self._tpot_ewma = 0.05        # s/token, seeds the retry-after hint
        self.recovery: dict | None = None   # set by recovery.recover_engine
        self._snap_id = 0
        self._snap_store = None       # lazy LogStructuredCheckpointStore
        # --- observability (repro.obs, DESIGN.md §12) ---------------------
        # ONE monotonic, test-pluggable clock for every engine timestamp:
        # admit_wall, dispatch timing, trace spans and metric rows share
        # this timebase, so queue-wait and compute splits are comparable.
        self.clock = clock if clock is not None else time.perf_counter
        self.tracer = tracer
        if tracer is not None:
            self.pool.attach_tracer(tracer)
            if self.journal is not None:
                self.journal.core.tracer = tracer
        self.calibration = (DeathCalibration(n_streams=self.streams)
                            if calibration else None)
        if self.calibration is not None:
            self.pool.enable_calibration(self.calibration)
        self.metrics_every = int(metrics_every)
        self._metrics_logger = (
            MetricsLogger(metrics_sink, clock=self.clock)
            if self.metrics_every and metrics_sink is not None else None)
        # per-dispatch phase attribution rows; recorded when phase_log=True
        # or a tracer is attached (bounded — old dispatches roll off)
        self.phase_log = bool(phase_log)
        self.dispatch_phases: collections.deque = collections.deque(
            maxlen=100_000)
        self._phase_acc: dict | None = None
        if warmup:
            self.warmup()

    # -------------------------------------------------------- mesh plumbing
    def _zeros_kv(self, shape):
        """Allocate a pool tensor directly under its sharding: each device
        materializes only its head-slice — never the full pool (which is the
        per-device-HBM win sharding exists for)."""
        if self._kv_shard is None:
            return jnp.zeros(shape, self.pool_dtype)
        return jax.jit(functools.partial(jnp.zeros, shape, self.pool_dtype),
                       out_shardings=self._kv_shard)()

    def _put_rep(self, x):
        """Upload host state, replicated across the mesh when sharded."""
        return jnp.asarray(x) if self._rep_shard is None else jax.device_put(
            np.asarray(x), self._rep_shard)

    def _mesh_ctx(self):
        """Mesh context for paths whose sharding is steered by logical-axis
        constraints resolved at trace time (prefill); null off-mesh."""
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def warmup(self) -> None:
        """Ahead-of-time compile of the serving hot paths (what production
        engines do at startup): the multi-step decode dispatch and one
        prefill + page-scatter per power-of-two prompt bucket.  All dispatch
        inputs are inert (inactive slots / trash pages), so warming mutates
        no served state.

        The prefix-hit continuation prefill is NOT warmed: its compile key
        is (shared pages, tail bucket, kv_len) — the exact prefix length is
        what makes hits bit-identical (DESIGN.md §7), and pre-compiling the
        combinatorial key space isn't feasible without knowing the
        workload's prefix lengths.  Hit shapes compile at first use; a
        steady workload reuses a handful of keys."""
        out, self.k_pools, self.v_pools, self._lens_dev, self._tok_dev = (
            self._decode(self.params, self.k_pools, self.v_pools,
                         self._bt_dev, self._lens_dev, self._tok_dev,
                         self._act_dev, np.int32(1)))
        out.block_until_ready()
        if self.async_compaction:
            # the pump owns the compaction move kernel, so its pow2 buckets
            # compile here, not inside a serving dispatch: sub-plans are
            # budget-capped, so the key space is known up front.  Trash→trash
            # moves are inert (only the trash page is written).
            bucket, top = 1, _pow2(max(self.clean_budget, self.pool.S))
            while bucket <= top:
                trash = np.full(bucket, self.trash_page, np.int32)
                self.k_pools, self.v_pools = self._move(
                    self.k_pools, self.v_pools, self._put_rep(trash),
                    self._put_rep(trash), use_pallas=self.use_pallas)
                bucket *= 2
            jax.block_until_ready(self.k_pools)
        T = self.page_T
        max_prompt_bucket = _pow2(self.max_pages_per_seq * T)
        if self.prefill_chunk:
            # chunked mode replaces the monolithic prefill family entirely:
            # warm one fused executable per prompt bucket (its compile key).
            # All inputs are inert — trash extent/chunk pages, inactive
            # decode slots — so warming writes only the trash page.
            C = self.prefill_chunk
            tb = _pow2(T)
            while tb <= max_prompt_bucket:
                nb = -(-tb // T)
                ext = np.full(nb, self.trash_page, np.int32)
                cpages = np.full(C // T, self.trash_page, np.int32)
                with self._mesh_ctx():
                    (out, _, self.k_pools, self.v_pools, self._lens_dev,
                     self._tok_dev) = self._fused(
                        self.params, self.k_pools, self.v_pools,
                        self._bt_dev, self._lens_dev, self._tok_dev,
                        self._act_dev, np.int32(1), self._put_rep(ext),
                        self._put_rep(cpages),
                        self._put_rep(np.zeros((1, C), np.int32)),
                        np.int32(0), np.int32(0), kv_len=tb)
                out.block_until_ready()
                tb *= 2
            return
        tb = _pow2(T)
        while tb <= max_prompt_bucket:
            n_pages = -(-tb // T)
            _, max_len = self._prefill_bucket(tb, n_pages)
            with self._mesh_ctx():
                first, ks, vs = self._prefill(
                    self.params, jnp.zeros((1, tb), jnp.int32), np.int32(1),
                    max_len=max_len)
            trash = np.full(max_len // T, self.trash_page, np.int32)
            self.k_pools, self.v_pools = self._scatter(
                self.k_pools, self.v_pools, ks, vs, self._put_rep(trash))
            tb *= 2

    # ----------------------------------------------- crash safety plumbing
    def _jrec(self, rec: dict) -> int | None:
        """Append one record to the session journal (no-op when off).
        Journal appends go through the same retry path as device ops — a
        transient journal fault is retried, a hard one crashes the engine
        (better to die than to serve unjournaled state)."""
        if self.journal is None:
            return None
        with self._span("journal"):
            return self._with_retries(
                "journal", lambda: self.journal.append_record(rec))

    def _with_retries(self, op: str, fn):
        """Run ``fn`` with fault injection keyed by ``op`` and bounded
        retry-with-backoff for :class:`TransientFault`.  Injection fires
        *before* ``fn`` — critically, before any jitted call consumes its
        donated buffers — so a failed attempt leaves the pools intact and
        the retry re-executes from unchanged state."""
        for attempt in range(self.fault_retries + 1):
            try:
                if self.injector is not None:
                    self.injector.check(self.dispatches, op=op)
                return fn()
            except TransientFault:
                if attempt == self.fault_retries:
                    raise
                self.fault_retries_done += 1
                delay = backoff_delay(attempt, base_s=self.fault_backoff_s)
                if delay > 0.0:
                    time.sleep(delay)
        raise AssertionError("unreachable")

    def _span(self, phase: str, row: bool = True, **args):
        """One engine phase: the span ``engine.<phase>`` on the profiler's
        timeline (:class:`repro.obs.span`).  With phase rows on
        (``phase_log=True`` or a tracer attached) the tracer records it
        too, and with ``row`` its wall time adds to the current dispatch's
        row for :meth:`phase_report` (several compactions or journal
        appends can land in one dispatch).  Spans outside that split
        (``step``, ``prefill``, ``emit``, and the alloc and host sync nested
        in admission) pass ``row=False``, so a row keeps the phases it
        had."""
        if self._phase_acc is None:
            return span(phase, "engine", **args)
        return self._row_span(phase, row, args)

    @contextlib.contextmanager
    def _row_span(self, phase: str, row: bool, args: dict):
        ph = self._phase_acc
        t = self.clock()
        try:
            with span(phase, "engine", self.tracer, **args) as late:
                yield late
        finally:
            if row:
                ph[phase] = ph.get(phase, 0.0) + self.clock() - t

    # ------------------------------------------------------------- requests
    def submit(self, prompt: np.ndarray, max_new_tokens: int) -> int:
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if (self.shed_queue_depth and self._admit_stalled
                and len(self.queue) >= self.shed_queue_depth):
            # overload: admission stalled past preemption AND the queue is
            # at depth — shed with a retry-after derived from the waiting
            # work at the measured decode rate (DESIGN.md §10)
            waiting = sum(
                self._predict_remaining(r.max_new_tokens, r.out_n)
                + len(self._eff_prompt(r))
                for q in (self._resume, self.queue) for r in q)
            self.shed_count += 1
            raise AdmissionShed(retry_after_estimate(waiting,
                                                     self._tpot_ewma))
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, np.asarray(prompt, np.int32),
                                  max_new_tokens))
        if self.tracer is not None:
            self.tracer.async_begin("req", rid, tid=1, cat="request",
                                    prompt_len=len(prompt),
                                    max_new=int(max_new_tokens))
        self._jrec({"t": "sub", "rid": rid,
                    "p": [int(t) for t in np.asarray(prompt)],
                    "n": int(max_new_tokens)})
        return rid

    def slot_active(self, i: int) -> bool:
        return self.rid[i] >= 0

    def slot_pages(self, i: int) -> np.ndarray:
        """Physical pages held by slot i (a view of the block-table row)."""
        return self.bt[i, :self.npages[i]]

    def has_work(self) -> bool:
        return (bool(self.queue) or bool(self._resume)
                or bool((self.rid >= 0).any()))

    def _prefill_bucket(self, plen: int, n_pages: int) -> tuple[int, int]:
        """(padded prompt length, prefill cache length) — the compile key.

        The prompt bucket is a power of two; the cache length is the
        smallest multiple of ``page_T`` covering both it and the
        power-of-two page bucket (so non-power-of-two page sizes reshape
        cleanly).
        """
        T = self.page_T
        tok_bucket = max(_pow2(plen), _pow2(T))
        max_len = max(_pow2(n_pages) * T, -(-tok_bucket // T) * T)
        return tok_bucket, max_len

    def _eff_prompt(self, req: Request) -> np.ndarray:
        """The token positions a (re)start must recompute K/V for: the
        prompt, plus — for a preempted request — the emitted tokens already
        *consumed* by decode (all but the last).  Used for admission
        sizing/estimation only: the actual restart prefills just the
        prompt and *re-decodes* the emitted span (see ``_start``)."""
        if req.out is None or req.out_n <= 1:
            return req.prompt
        return np.concatenate([req.prompt,
                               req.out[:req.out_n - 1].astype(np.int32)])

    def _predict_remaining(self, max_new: int, emitted: int) -> int:
        """Tokens a request is *predicted* to still emit.  Exact
        (``max_new - emitted``) when stop tokens are off; otherwise the
        length predictor's estimate, clamped to [1, tokens-left]."""
        cap = max(max_new - emitted, 1)
        if self.stop_token is None:
            return cap
        pred = self.length_predictor.predict(max_new)
        return int(np.clip(pred - emitted, 1, cap))

    def _pages_needed(self, req: Request) -> int:
        """Pages admission control reserves for this request (prompt +
        consumed resume tokens + remaining output), gross of any cached
        prefix.  Admission is *optimistic* — the predicted output length
        instead of the max_new_tokens worst case — only when preemption is
        on: an under-prediction then surfaces as pool pressure the
        scheduler relieves by preempting, whereas without the backstop it
        would be an OOM, so the conservative bound is kept."""
        plen_eff = len(self._eff_prompt(req))
        rem = (self._predict_remaining(req.max_new_tokens, req.out_n)
               if self.preemption else max(req.max_new_tokens - req.out_n, 1))
        return (plen_eff + rem + self.page_T - 1) // self.page_T

    def _gate_avail(self, hit_pages: list[int]) -> int:
        """Blocks the admission gate may count: free blocks, plus cached
        prefixes reclaimable on demand (the pool's pressure hook evicts
        unreferenced leaves before OOM) — minus the matched pages only the
        tree still references, which the request is about to splice, not
        reclaim."""
        avail = self.pool.free_blocks()
        # fenced victim slabs are reclaimable on demand exactly like
        # evictable cache pages: the alloc path drains the async pipeline
        # when frames run genuinely short, so counting them here keeps
        # fencing from starving admission into needless preemption
        avail += self.pool.core.fenced_count() * self.pool.S
        if self.prefix_cache is not None:
            # cached ids may be stale across a pending remap — resolve first
            overlap = int((self.pool.block_ref[self.pool.resolve(
                np.asarray(hit_pages, np.int64))] == 1).sum()) \
                if hit_pages else 0
            avail += max(self.prefix_cache.evictable() - overlap, 0)
        return avail

    def _admit(self) -> None:
        started: list[int] = []
        self._admit_stalled = False
        free = np.flatnonzero(self.rid < 0)
        for i in free:
            if self._pf is not None:
                # chunked mode admits one prefill at a time: the next
                # request starts the dispatch after this one's final chunk
                # lands (admission runs every step(), so nothing waits
                # longer than the chunk cadence)
                break
            # preempted requests resume first — they were admitted once and
            # already carry emitted tokens the caller is waiting on
            q = self._resume if self._resume else self.queue
            if not q:
                break
            req = q[0]
            worst = (len(req.prompt) + req.max_new_tokens + self.page_T - 1
                     ) // self.page_T
            if worst > self.max_pages_per_seq:
                raise ValueError("request exceeds max_seq")
            need = self._pages_needed(req)
            hit_pages: list[int] = []
            if self.prefix_cache is not None:
                # a cached prefix will be spliced, not allocated: the
                # request's real allocation need is net of the match
                # (matched on the prompt — what _start actually prefills)
                hit_pages = self.prefix_cache.match(req.prompt)
                need -= len(hit_pages)
            # the compaction reserve is compact_trigger *slabs* (see
            # admission_reserve) — waived when nothing is active, so a
            # request sized to the whole pool can still run alone
            reserve = (self.pool.admission_reserve()
                       if (self.rid >= 0).any() else 0)
            avail = self.pool.free_blocks()
            if avail < need + reserve and self.prefix_cache is not None:
                avail = self._gate_avail(hit_pages)
            if avail < need + reserve and self.preemption:
                self._preempt_for(need + reserve - avail, keep=started)
                avail = self._gate_avail(hit_pages)  # re-measured gate
            if avail < need + reserve:
                # admission control: wait for deaths/compaction.  The
                # stall is what arms load shedding — capacity, not a
                # momentarily empty free list, is the bottleneck here
                self._admit_stalled = True
                break
            q.popleft()
            try:
                self._start(int(i), req, from_resume=q is self._resume)
            except TransientFault:
                # transactional admission: _start already unwound its page
                # references; put the request back at the head and retry
                # at the next step() (the injector re-rolls per call)
                self.fault_unwinds += 1
                q.appendleft(req)
                break
            started.append(int(i))

    def _preempt_for(self, deficit: int, *, keep=(),
                     min_active: int = 0) -> int:
        """Free at least ``deficit`` blocks by preempting running
        sequences, chosen by the declining-cost key (policies.key_preempt:
        cheap recompute, many exclusively-held pages, long predicted
        remaining lifetime first).  Returns the blocks actually freed.

        Progress is *measured* (free blocks + evictable cache pages), not
        estimated from the victims' refcounts: a page freed mid-way into a
        still-OPEN lifetime-bucket slab is neither appendable (slots are
        append-only) nor compactable (victims must be sealed) until its
        slab drains, so trusting the per-victim estimate could pass
        admission on blocks the allocator cannot actually hand out.

        ``keep``: slots never picked (sequences admitted in the current
        pass — preempting them before they decode a token would churn).
        ``min_active``: stop before the active count would drop below this
        (the growth path keeps the last sequence running: preempting a
        sequence to fund its *own* growth would loop forever)."""
        def avail() -> int:
            a = self.pool.free_blocks()
            a += self.pool.core.fenced_count() * self.pool.S
            if self.prefix_cache is not None:
                a += self.prefix_cache.evictable()
            return a

        # committing the async pipeline frees fenced slabs without evicting
        # anyone — always cheaper than preemption, so it goes first
        if self.pool.on_drain is not None and self.pool.deferred_moves():
            self.pool.on_drain()
        start = avail()
        keep = set(int(k) for k in keep)
        while avail() - start < deficit:
            cand = np.array([c for c in np.flatnonzero(self.rid >= 0)
                             if int(c) not in keep], dtype=np.int64)
            if len(cand) == 0 or int((self.rid >= 0).sum()) <= min_active:
                break
            # pages whose *last* reference a preemption drops (shared
            # prefix pages survive in the tree / other referencers)
            freeable = np.array(
                [int((self.pool.block_ref[self.pool.resolve(
                    self.bt[j, :self.npages[j]].astype(np.int64))] == 1).sum())
                 for j in cand])
            remaining = np.array(
                [self._predict_remaining(
                    int(self._out_n[j] + self.to_gen[j]),
                    int(self._out_n[j])) for j in cand])
            v = choose_preempt_victims(1, recompute=self.lens[cand],
                                       freeable=freeable,
                                       remaining=remaining)
            if len(v) == 0:
                break  # nothing preemptible frees any page
            self._preempt(int(cand[v[0]]))
        return max(avail() - start, 0)

    def _start(self, i: int, req: Request, from_resume: bool = False) -> None:
        # A resume (req.out carries emitted tokens) restarts a preempted or
        # recovered sequence *from scratch*: the ORIGINAL prompt goes
        # through the exact prefill a fresh admission runs (same token
        # bucket, same kernel → bit-identical K/V), and decode then
        # re-derives the already-emitted span deterministically.
        # Re-prefilling the consumed tokens instead would compute their K/V
        # with prefill arithmetic where the original used decode arithmetic
        # — close, but not bit-equal (different reduction shapes under the
        # activation dtype), and a later near-tie argmax can flip.
        # ``_jskip`` records how many output tokens were already journaled
        # and delivered, so the replayed span is not re-recorded.
        resume = req.out is not None and req.out_n > 0
        prompt = req.prompt
        plen = len(prompt)
        T = self.page_T
        n_pages = (plen + T - 1) // T
        # §5.3 placement estimator: blocks die when their sequence finishes
        # ⇒ expected death clock = now + blocks that will die then (the
        # re-decoded span counts: those writes happen again).  With stop
        # tokens, output length is data-dependent and this becomes the
        # length predictor's estimate, not ground truth (DESIGN.md §8).
        est = (self.pool.u_now + plen + max(req.out_n - 1, 0)
               + self._predict_remaining(req.max_new_tokens, req.out_n))

        # --- shared-prefix lookup: splice cached full pages (the lookup is
        # CoW-capped: at least one prompt token is always prefilled, and a
        # fully-matched final page is recomputed privately — DESIGN.md §7)
        n_shared = 0
        if self.prefix_cache is not None:
            hit = self.prefix_cache.lookup(prompt)
            n_shared = len(hit)
            if n_shared:
                shared = np.asarray(hit, np.int64)
                # one reference per referencing sequence; the death estimate
                # becomes the max over referencers (shared prefixes sort
                # into long-lifetime slabs)
                self.pool.incref_pages(shared, est)
                # park the shared ids in the block table *before* the tail
                # alloc: a compaction fired by it remaps this row too
                self.bt[i, :] = self.trash_page
                self.bt[i, :n_shared] = shared
                self.npages[i] = n_shared

        # batched alloc: any compaction fires (and remaps the *other* slots'
        # pages via the callback) before these page ids are handed out.  If
        # the pool still OOMs, the just-taken prefix references must be
        # given back (rid[i] is not set yet, so no _finish would ever
        # decref them) — otherwise every failed admission of a hitting
        # prompt would permanently inflate the shared pages' refcounts.
        try:
            with self._span("alloc", row=False):
                pages_new = self.pool.alloc_blocks(
                    np.full(n_pages - n_shared, req.rid, dtype=np.int64),
                    Placement(est_death=est))
        except Exception:
            if n_shared:
                self.pool.free_pages(self.bt[i, :n_shared].astype(np.int64))
                self.bt[i, :] = self.trash_page
                self.npages[i] = 0
                self._bt_dirty = True
            raise
        if n_shared == 0:
            self.bt[i, :] = self.trash_page
        self.bt[i, n_shared:n_pages] = pages_new
        self.npages[i] = n_pages

        # fault-injection point for the prefill path — *before* any device
        # work touches the donated pools, so unwinding is pure host-side
        # bookkeeping: drop every reference this admission took (shared
        # prefix pages survive for their other holders) and re-raise;
        # _admit requeues the request on a TransientFault
        if self.injector is not None:
            try:
                self.injector.check(self.dispatches, op="prefill")
            except BaseException:
                self.pool.free_pages(self.bt[i, :n_pages].astype(np.int64))
                self.bt[i, :] = self.trash_page
                self.npages[i] = 0
                self._bt_dirty = True
                raise

        # admission bookkeeping shared by both prefill modes.  ``resumes``
        # counts resume-queue restarts (not just emitted-token carriers):
        # a chunked prefill can be preempted before its first token, and
        # its restart is a resume too — which is what keeps the
        # ``resumes == preemptions`` ledger exact at drain.
        self.admit_wall.setdefault(req.rid, self.clock())
        if self.tracer is not None:
            self.tracer.async_instant(
                "req.resume" if from_resume else "req.admit",
                req.rid, tid=1, cat="request")
        if from_resume:
            self.resumes += 1
        if resume:
            # prompt re-prefilled + consumed output tokens re-decoded
            self.recomputed_tokens += plen + req.out_n - 1
        self._prefill_tokens_total += plen
        if n_shared:
            self._prefill_tokens_saved += n_shared * T
        # admission record: replay re-prioritizes the request (it was
        # running, so recovery resumes it before fresh queue entries);
        # slot/pages are forensic — physical placement is rebuilt, not
        # replayed (page contents died with device HBM)
        self._jrec({"t": "adm", "rid": req.rid, "slot": int(i),
                    "res": int(resume), "shr": int(n_shared),
                    "pg": [int(p) for p in pages_new]})

        if self.prefill_chunk:
            # chunked mode: park the slot in the *prefilling* state; step()
            # feeds one chunk per fused dispatch until _pf_complete
            with self._span("prefill", row=False):
                self._start_chunked(i, req, prompt, plen, n_pages, n_shared,
                                    est)
            return

        # dense prefill -> scatter K/V into the allocated pages.  Prompt and
        # cache lengths are bucketed to powers of two so distinct prompt
        # lengths reuse one compiled prefill per bucket; the true length is
        # traced (dynamic last-token slice), not baked into the compile key.
        # On a prefix hit, only the uncached tail is computed: the tail
        # prefill attends the cached prefix K/V gathered straight from the
        # pool pages (exact-length, so key positions align absolutely and
        # the arithmetic matches a cold prefill row-for-row).
        with self._span("prefill", row=False):
            if n_shared:
                tlen = plen - n_shared * T
                tok_bucket, max_len = self._prefill_bucket(tlen,
                                                           n_pages - n_shared)
                toks = np.zeros(tok_bucket, np.int32)
                toks[:tlen] = prompt[n_shared * T:]
                # the prefix's pages as the alloc's compaction left them
                prefix_pages = self.bt[i, :n_shared].astype(np.int32)
                # kv_len = the bucket a cold full prefill of this prompt
                # would attend over: identical key extents are what make
                # the hit arithmetic bit-identical (gqa_prefill_cont's
                # dtype/tiling note)
                kv_len = self._prefill_bucket(plen, n_pages)[0]
                with self._mesh_ctx():
                    first_tok, ks, vs = self._prefill_cont(
                        self.params, self.k_pools, self.v_pools,
                        self._put_rep(prefix_pages),
                        jnp.asarray(toks)[None], np.int32(tlen),
                        max_len=max_len, kv_len=kv_len)
            else:
                tok_bucket, max_len = self._prefill_bucket(plen, n_pages)
                toks = np.zeros(tok_bucket, np.int32)
                toks[:plen] = prompt
                with self._mesh_ctx():
                    first_tok, ks, vs = self._prefill(
                        self.params, jnp.asarray(toks)[None],
                        np.int32(plen), max_len=max_len)
            nb = max_len // T
            # scatter the whole bucket; pages beyond the allocation land in
            # the trash page, so the compile key is the bucket size, not
            # n_pages
            pages_pad = np.full(nb, self.trash_page, np.int32)
            pages_pad[:len(pages_new)] = pages_new
            self.k_pools, self.v_pools = self._scatter(
                self.k_pools, self.v_pools, ks, vs,
                self._put_rep(pages_pad))

        # register this prompt's full (immutable) pages for future sharing;
        # already-cached keys keep their existing page, so a recomputed
        # boundary page simply stays private to this sequence
        if self.prefix_cache is not None and plen // T:
            self.prefix_cache.insert(prompt,
                                     self.bt[i, :plen // T].copy(), est)

        # the prefill's first token: the host waits here for the device
        with self._span("host_sync", row=False):
            first = int(first_tok[0])
        self.rid[i] = req.rid
        self.lens[i] = plen
        self._prompt[i] = req.prompt
        self.tokens[i] = first
        self.to_gen[i] = req.max_new_tokens - 1
        if resume:
            # keep the carried buffer: decode re-emits the same tokens
            # bit-identically, and a mid-replay preempt or snapshot must
            # still see the full known span (via _jskip)
            out = req.out
            self._jskip[i] = req.out_n
        else:
            out = np.empty(req.max_new_tokens, np.int32)
            self._jskip[i] = 0
        out[0] = self.tokens[i]
        self._out[i] = out
        self._out_n[i] = 1
        if not resume:
            # the prefill's first output token is journaled before any
            # finish record this admission could produce (cap/stop below);
            # a resume's first token was journaled by its original start
            self._jrec({"t": "first", "rid": req.rid, "tok": first})
        self._bt_dirty = self._state_dirty = True
        # the prefill token may already complete the request: cap reached,
        # or (stop-token decode) the first emitted token is the stop token
        if self.to_gen[i] <= 0 or (not resume and self.stop_token is not None
                                   and self.tokens[i] == self.stop_token):
            self._admit_done.append(req.rid)
            self._finish(i)

    def _start_chunked(self, i: int, req: Request, prompt: np.ndarray,
                       plen: int, n_pages: int, n_shared: int,
                       est: float) -> None:
        """Park slot ``i`` in the *prefilling* state (DESIGN.md §9): its
        pages are allocated (and a cached prefix spliced) exactly like a
        monolithic start, but instead of one dense prefill, ``step()``
        feeds one ``prefill_chunk``-token chunk per fused dispatch until
        the final chunk lands and :meth:`_pf_complete` graduates the slot
        into decode.  The slot owns its rid/pages/prompt from the first
        chunk — so preemption mid-prefill and the OOM unwind go through
        the same decref paths as a decoding slot — but stays masked out of
        the decode active set."""
        T = self.page_T
        self.rid[i] = req.rid
        self._prompt[i] = req.prompt
        self._out[i] = req.out
        self._out_n[i] = req.out_n
        self._jskip[i] = 0         # parked: _out_n itself is the known span
        self.tokens[i] = 0
        self.to_gen[i] = req.max_new_tokens - req.out_n
        # lens tracks prefill progress (chunk boundary = page boundary, so
        # a cached prefix starts the clock at n_shared * T); the slot is
        # decode-masked, so the device-side value is never consumed
        self.lens[i] = n_shared * T
        self._prefilling[i] = True
        self._pf = dict(slot=i, prompt=prompt, plen=plen,
                        pos=n_shared * T,
                        # the full prompt's pow2 token bucket — the fused
                        # dispatch's compile key AND the key extent every
                        # chunk attends over, matching the monolithic
                        # prefill's tiling exactly (bit-identity)
                        kv_len=self._prefill_bucket(plen, n_pages)[0],
                        est=est,
                        resume=req.out is not None and req.out_n > 0,
                        max_new=req.max_new_tokens)
        self._bt_dirty = self._state_dirty = True

    def _pf_complete(self, first_tok: int) -> int | None:
        """The final chunk landed: graduate the prefilling slot into the
        decode active set.  Returns the request id if the prefill token
        already completed the request (cap reached / stop token), else
        None.  The prefix-cache insert is deferred to here — an in-flight
        prefill's later pages hold garbage another request must not
        splice."""
        pf = self._pf
        i = pf["slot"]
        self._pf = None
        self._prefilling[i] = False
        self.lens[i] = pf["plen"]
        if self.prefix_cache is not None and pf["plen"] // self.page_T:
            self.prefix_cache.insert(
                pf["prompt"], self.bt[i, :pf["plen"] // self.page_T].copy(),
                pf["est"])
        if pf["resume"]:
            # graduation of a resumed slot: decode re-derives the emitted
            # span bit-identically; mark it so it is not re-journaled
            self._jskip[i] = int(self._out_n[i])
            self.tokens[i] = int(first_tok)
            self.to_gen[i] = pf["max_new"] - 1
            self._out[i][0] = first_tok   # bit-identical to the recorded one
            self._out_n[i] = 1
        else:
            self._jskip[i] = 0
            self.tokens[i] = int(first_tok)
            self.to_gen[i] = pf["max_new"] - 1
            out = np.empty(pf["max_new"], np.int32)
            out[0] = first_tok
            self._out[i] = out
            self._out_n[i] = 1
            self._jrec({"t": "first", "rid": int(self.rid[i]),
                        "tok": int(first_tok)})
        self._state_dirty = True
        if self.to_gen[i] <= 0 or (not pf["resume"]
                                   and self.stop_token is not None
                                   and self.tokens[i] == self.stop_token):
            rid = int(self.rid[i])
            self._finish(i)
            return rid
        return None

    def _release_slot(self, i: int) -> None:
        """Free slot i's pages (one decref each — shared prefix pages
        survive for their other referencers) and reset its state."""
        if self._pf is not None and self._pf["slot"] == i:
            self._pf = None          # abandon the in-flight prefill
        self._prefilling[i] = False
        self._jrec({"t": "rel", "rid": int(self.rid[i]),
                    "pg": [int(p) for p in self.slot_pages(i)]})
        self.pool.free_pages(self.slot_pages(i).astype(np.int64))
        self.bt[i, :] = self.trash_page
        self.rid[i] = -1
        self.lens[i] = self.to_gen[i] = self.npages[i] = 0
        self.tokens[i] = 0
        self._out[i] = None
        self._out_n[i] = 0
        self._jskip[i] = 0
        self._prompt[i] = None
        self._bt_dirty = self._state_dirty = True

    def _finish(self, i: int) -> None:
        rid = int(self.rid[i])
        self.finished[rid] = self._out[i][:self._out_n[i]].tolist()
        self.length_predictor.observe(int(self._out_n[i]))
        if self.tracer is not None:
            self.tracer.async_end("req", rid, tid=1, cat="request",
                                  tokens=int(self._out_n[i]))
        self._jrec({"t": "fin", "rid": rid})
        self._release_slot(i)

    def _preempt(self, i: int) -> None:
        """Evict a running sequence under pressure: drop its page
        references and requeue it carrying its emitted tokens — onto the
        resume queue, which `_admit` serves FIFO and *before* any new
        admission; a later `_start` re-prefills the prompt and re-decodes
        the emitted span, bit-identically with never having been
        preempted."""
        self.preemptions += 1
        if self.tracer is not None:
            self.tracer.async_instant("req.preempt", int(self.rid[i]),
                                      tid=1, cat="request")
        self._jrec({"t": "pre", "rid": int(self.rid[i])})
        # a slot preempted mid-replay (out_n < _jskip) still *knows* the
        # full journaled span — the carried buffer holds it past out_n
        self._resume.append(Request(
            int(self.rid[i]), self._prompt[i],
            int(self._out_n[i] + self.to_gen[i]),   # original max_new_tokens
            out=self._out[i],
            out_n=int(max(self._out_n[i], self._jskip[i]))))
        self._release_slot(i)

    # ---------------------------------------------------------------- step
    def _sync_device(self) -> None:
        """Upload host state that an event dirtied since the last dispatch."""
        if self._bt_dirty:
            self._bt_dev = self._put_rep(self.bt)
            self._bt_dirty = False
        if self._state_dirty:
            self._lens_dev = self._put_rep(self.lens)
            self._lens_on_dev = self.lens.copy()
            self._tok_dev = self._put_rep(self.tokens)
            # a prefilling slot is NOT decode-active: the fused dispatch
            # writes its chunk K/V while decode skips it until the final
            # chunk graduates it (_pf_complete)
            self._act_dev = self._put_rep((self.rid >= 0) & ~self._prefilling)
            self._state_dirty = False

    def _event_horizon(self, active: np.ndarray) -> int:
        """Tokens until the earliest host event: a slot crossing into an
        unallocated page (computed from ``seq_len % page_T``) or finishing.

        The horizon is *exact* without stop tokens: nothing can finish or
        free pages before it, so a waiting arrival is admitted at the
        earliest possible dispatch already.  With stop-token decode an
        active slot can exit mid-dispatch invisibly — the device freezes
        it but the host only learns at dispatch end, so a queued arrival
        sits out the rest of the dispatch with a slot (and its pages)
        effectively free.  ``admit_every_dispatch`` (default) closes that
        window: with work waiting under stop-token decode, dispatches
        shrink to per-token scheduling (n=1, the continuous-batching
        iteration grain) so every exit is seen — and admission re-run —
        at the next token.  The flag is the dial between admission latency
        and the multi-token dispatch's host-overhead amortization."""
        if active.any():
            room = self.npages * self.page_T - self.lens
            until = np.minimum(room, self.to_gen)[active]
            n = min(int(until.min()), self.max_decode_chunk)
        else:
            n = 1
        if (self.admit_every_dispatch and self.stop_token is not None
                and (self.queue or self._resume)):
            n = 1
        return max(n, 1)

    def step(self) -> list[int]:
        """Admit, then decode up to ``max_decode_chunk`` tokens for every
        active slot in one device dispatch.  Returns finished request ids.

        With a tracer attached or ``phase_log=True``, each dispatch is
        split into attributed phases (admit / alloc / upload / dispatch /
        host_sync, plus compaction and journal time accumulated wherever
        they fire) — the latency breakdown the overload bench reports.
        Disabled (the default), the apparatus is one ``None`` check plus
        the ``engine.*`` profiler annotations (:meth:`_span`)."""
        tr = self.tracer
        ph = {} if (self.phase_log or tr is not None) else None
        self._phase_acc = ph
        t_step = self.clock()
        try:
            with self._span("step", row=False, dispatch=self.dispatches):
                return self._step_impl()
        finally:
            self._phase_acc = None
            if tr is not None:
                tr.counter("pool", free_blocks=self.pool.free_blocks(),
                           queue_depth=len(self.queue) + len(self._resume),
                           active_slots=int((self.rid >= 0).sum()))
            if ph is not None and ph.pop("dispatched", False):
                ph["total"] = self.clock() - t_step
                self.dispatch_phases.append(ph)
            if (self._metrics_logger is not None
                    and self.dispatches % self.metrics_every == 0):
                self._sample_metrics()

    def _step_impl(self) -> list[int]:
        if self.async_compaction:
            # commit last step's in-flight remaps, plan + issue new moves
            # ahead of admission — cleaning leaves the dispatch path
            self._pump_compaction()
        with self._span("admit"):
            self._admit()
        done, self._admit_done = self._admit_done, []
        active = (self.rid >= 0) & ~self._prefilling
        pf = self._pf
        if not active.any() and pf is None:
            return done
        self.dispatches += 1
        t0 = self.clock()
        if self._phase_acc is not None:
            self._phase_acc["dispatched"] = True

        # pages for the incoming tokens must exist before the dispatch writes
        # them; one batched alloc covers every slot at a page boundary
        # (compaction, if it fires, remaps held pages first).  With stop
        # tokens, est_death underestimates can push growth past the
        # admission reserve: preemption is the backstop before the pool
        # would OOM — but never of the last active sequence (preempting a
        # sequence to fund its own growth would loop forever).
        growing = np.flatnonzero(active
                                 & (self.lens >= self.npages * self.page_T))
        if growing.size and self.preemption:
            avail = self.pool.free_blocks()
            if self.prefix_cache is not None:
                avail += self.prefix_cache.evictable()
            if avail < growing.size:
                self._preempt_for(growing.size - avail, min_active=1)
                active = (self.rid >= 0) & ~self._prefilling
                pf = self._pf  # the in-flight prefill may have been evicted
                growing = np.flatnonzero(
                    active & (self.lens >= self.npages * self.page_T))
                if not active.any() and pf is None:
                    return done
        if growing.size:
            with self._span("alloc"):
                rem = np.array([self._predict_remaining(
                    int(self._out_n[j] + self.to_gen[j]),
                    int(self._out_n[j])) for j in growing])
                pages = self.pool.alloc_blocks(
                    self.rid[growing],
                    Placement(est_death=self.pool.u_now
                              + (self.lens[growing] + rem).astype(np.float64)))
                self.bt[growing, self.npages[growing]] = pages
                self.npages[growing] += 1
                self._bt_dirty = True
                self._jrec({"t": "al", "r": self.rid[growing].tolist(),
                            "pg": pages.tolist()})

        n = self._event_horizon(active)
        with self._span("upload"):
            self._sync_device()
        if pf is not None:
            # ---- fused dispatch: one prefill chunk + n decode tokens ----
            C, T = self.prefill_chunk, self.page_T
            pi, pos = pf["slot"], pf["pos"]
            seg = pf["prompt"][pos:pos + C]
            ptoks = np.zeros(C, np.int32)
            ptoks[:len(seg)] = seg
            is_last = pos + C >= pf["plen"]
            last_idx = min(pf["plen"] - 1 - pos, C - 1) if is_last else 0
            # full key extent = the slot's block-table row, trash-padded to
            # the kv_len bucket (rows past the allocation are never read)
            nb = -(-pf["kv_len"] // T)
            ext = np.full(nb, self.trash_page, np.int32)
            m = min(nb, self.max_pages_per_seq)
            ext[:m] = self.bt[pi, :m]
            # the chunk's own pages; a final chunk's tail past the
            # allocation scatters into the trash page
            cpages = np.full(C // T, self.trash_page, np.int32)
            p0 = pos // T
            for j in range(C // T):
                if p0 + j < self.npages[pi]:
                    cpages[j] = self.bt[pi, p0 + j]
            def _dispatch_fused():
                with self._mesh_ctx():
                    return self._fused(
                        self.params, self.k_pools, self.v_pools,
                        self._bt_dev, self._lens_dev, self._tok_dev,
                        self._act_dev, np.int32(n), self._put_rep(ext),
                        self._put_rep(cpages), self._put_rep(ptoks[None]),
                        np.int32(pos), np.int32(last_idx),
                        kv_len=pf["kv_len"])
            with self._span("dispatch"):
                (out, first, self.k_pools, self.v_pools, self._lens_dev,
                 self._tok_dev) = self._with_retries("dispatch",
                                                     _dispatch_fused)
            pf["pos"] = pos + C
            # host-only progress marker (the slot is decode-masked, so the
            # stale device-side value is never consumed — no upload)
            self.lens[pi] = min(pf["pos"], pf["plen"])
            self.prefill_chunks_dispatched += 1
            if self.tracer is not None:
                self.tracer.async_instant(
                    "req.prefill_chunk", int(self.rid[pi]), tid=1,
                    cat="request", pos=int(pf["pos"]))
        else:
            is_last = False
            with self._span("dispatch"):
                (out, self.k_pools, self.v_pools, self._lens_dev,
                 self._tok_dev) = self._with_retries(
                    "dispatch",
                    lambda: self._decode(self.params, self.k_pools,
                                         self.v_pools, self._bt_dev,
                                         self._lens_dev, self._tok_dev,
                                         self._act_dev, np.int32(n)))
        # ONE host sync per dispatch, not per token
        with self._span("host_sync"):
            toks = self._with_retries("host_sync",
                                      lambda: np.asarray(out))[:n]
        with self._span("emit", row=False):
            return self._emit(toks, n, active, done, t0,
                              first if is_last else None)

    def _emit(self, toks, n, active, done, t0, first) -> list[int]:
        """After the dispatch's host sync: copy out the emitted tokens,
        journal them, finish and release completed slots, graduate a
        prefill whose final chunk landed (``first``: its device buffer),
        and update the decode-rate EWMA.  Returns the finished ids."""
        # host bookkeeping: O(active slots) per dispatch.  With stop tokens
        # a slot may have stopped mid-dispatch: it emitted tokens only up to
        # and including its first stop token (the device froze it there), so
        # the per-slot emitted count comes out of the same token buffer.
        act = np.flatnonzero(active)
        emitted = np.full(self.max_batch, n, np.int32)
        stopped = np.zeros(self.max_batch, bool)
        if self.stop_token is not None and act.size:
            hit = toks[:, act] == self.stop_token          # (n, |act|)
            has = hit.any(axis=0)
            emitted[act[has]] = hit.argmax(axis=0)[has] + 1
            stopped[act[has]] = True
        if self.use_pallas:
            self._count_attn_pages(n, np.where(active, emitted, 0))
        for i in act:
            e = int(emitted[i])
            w = self._out_n[i]
            self._out[i][w:w + e] = toks[:e, i]
            self._out_n[i] += e
            self.lens[i] += e            # matches the device: seq_lens froze
            self.to_gen[i] -= e          # with the active mask at the stop
            self.tokens[i] = int(toks[e - 1, i])

        # the emitted tokens are journaled BEFORE any fin record below:
        # replay must never see a finish whose completing tokens were lost
        # to the crash (a fin with no emit would drop output).  A resumed
        # slot's re-decoded span (indices < _jskip) was journaled by its
        # original run and is sliced off — replay appends emits blindly,
        # so re-recording it would duplicate tokens.
        if act.size:
            spans = []
            for i in act:
                e = int(emitted[i])
                b = int(self._out_n[i]) - e
                s = max(b, int(self._jskip[i]))
                spans.append([int(t) for t in self._out[i][s:b + e]])
            if any(spans):
                self._jrec({"t": "emit",
                            "r": [int(self.rid[i]) for i in act],
                            "k": spans})

        for i in act:
            if stopped[i] or self.to_gen[i] <= 0:
                done.append(int(self.rid[i]))
                self._finish(int(i))

        if first is not None:
            with self._span("host_sync"):
                first = int(np.asarray(first)[0])
            fin = self._pf_complete(first)
            if fin is not None:
                done.append(fin)

        if act.size:
            tot = int(emitted[act].sum())
            if tot > 0:   # decode-rate EWMA feeds the shed retry-after hint
                dt = self.clock() - t0
                self._tpot_ewma = 0.8 * self._tpot_ewma + 0.2 * (dt / tot)
        if (self.journal is not None and self.snapshot_every
                and self.dispatches % self.snapshot_every == 0):
            self.snapshot()
        if self.audit_every and self.dispatches % self.audit_every == 0:
            self.audit()
        return done

    def _count_attn_pages(self, n: int, emitted: np.ndarray) -> None:
        """Add one dispatch's ``n`` decode iterations to the kernel's page
        counters.  A slot that emitted ``e`` tokens attended its device
        length + 1 in iterations 0..e-1, growing by one each, and nothing
        after it stopped; an inactive slot attends nothing."""
        it = np.arange(n)[:, None]
        lens = np.where(it < emitted, self._lens_on_dev + it + 1, 0)  # (n, B)
        self.attn_pages_read += int(pages_fetched(lens, self.page_T).sum())
        self.attn_pages_table += n * self.bt.size
        self._lens_on_dev += emitted

    def run_to_completion(self, max_steps: int = 100_000) -> dict:
        for _ in range(max_steps):
            self.step()
            if not self.has_work():
                break
        return self.finished

    # ----------------------------------------------------------- compaction
    def _move_plan(self, plan) -> None:
        """Journal + issue the jitted donated move for one plan.  The pool's
        accounting already committed the placement, so the tensor move
        cannot be abandoned — transient faults retry in place until the
        move lands or the retry budget declares the fault hard."""
        # pad the plan to a power-of-two bucket with trash→trash moves so
        # plan sizes share compiled executables
        src, dst = plan.padded(_pow2(len(plan)), self.trash_page)
        self._jrec({"t": "mv", "src": plan.src_pages.tolist(),
                    "dst": plan.dst_pages.tolist()})
        self.k_pools, self.v_pools = self._with_retries(
            "compaction",
            lambda: self._move(self.k_pools, self.v_pools,
                               self._put_rep(src), self._put_rep(dst),
                               use_pallas=self.use_pallas))

    def _apply_remap(self, plan) -> None:
        """Remap block tables: one vectorized page-id lookup over the
        matrix.  Every reference holder remaps with the same LUT — all slot
        rows (shared pages appear in several) and the prefix-cache tree."""
        lut = np.arange(self.trash_page + 1, dtype=np.int32)
        lut[plan.src_pages] = plan.dst_pages
        self.bt = lut[self.bt]
        if self.prefix_cache is not None:
            self.prefix_cache.remap(lut)
        self._bt_dirty = True

    def _execute_plan(self, plan) -> None:
        """Synchronous path (``pool.on_compaction``): move + remap, run to
        completion before the pool hands out any plan-freed page id."""
        if len(plan) == 0:
            return
        with self._span("compaction", moves=len(plan)):
            self._move_plan(plan)
            self._apply_remap(plan)

    # --- async pipeline: planned → in-flight → committed (DESIGN.md §13) --
    def _commit_plan(self, plan) -> None:
        """Commit one in-flight sub-plan: apply its LUT remap to every
        external holder, journal the commit ("mvc" — forensic: a kill
        between "mv" and "mvc" recovers via replay, which rebuilds physical
        placement from scratch), and release its fenced victims."""
        if len(plan):
            self._apply_remap(plan)
            self._jrec({"t": "mvc", "src": plan.src_pages.tolist(),
                        "dst": plan.dst_pages.tolist()})
        self.pool.commit_plan(plan)

    def _hot_pages(self) -> np.ndarray:
        """Pages the *upcoming* dispatch may write: each live slot's pages
        from its current length on (decode appends K/V there), including a
        prefilling slot's chunk span.  A planned move whose source
        intersects this set cannot leave its remap pending across the
        dispatch — the decode would write the source after the move copied
        it, and the write would be lost at remap."""
        hot = []
        for i in np.flatnonzero(self.rid >= 0):
            lo = int(self.lens[i]) // self.page_T
            if self._pf is not None and self._pf["slot"] == i:
                lo = min(lo, int(self._pf["pos"]) // self.page_T)
            hot.append(self.bt[i, lo:self.npages[i]].astype(np.int64))
        return (np.concatenate(hot) if hot
                else np.empty(0, dtype=np.int64))

    def _pump_compaction(self) -> None:
        """The per-step async-cleaning pump, run before admission:

        1. **commit** — sub-plans whose move dispatch was issued last step
           apply their LUT remap now (the next sync point after the move:
           the remapped tables upload with this step's ``_sync_device``)
           and release their fenced victims.  FIFO: the pending LUT and
           chained moves (a later plan may relocate an earlier plan's
           destination) compose in plan order only.
        2. **issue** — dispatch pending sub-plans' moves up to the
           scheduler's deficit-weighted clean budget, double-buffered
           against this step's decode dispatch.  A sub-plan whose source
           intersects the dispatch's write set commits immediately instead
           (the move is device-ordered before the decode, so remapping
           first is always safe) — rare, but it is what keeps deferred
           remaps write-hazard-free.

        The pump deliberately does NOT plan.  Victim slabs become
        cycle-worthy *mid-admission* — sealed by the very writes that drain
        the reserve — so no step-boundary planner can see them; planning
        lives in the alloc path (``_compact_until``), where it runs at
        exactly the state synchronous cleaning used to, picking the same
        victims at the same Wamp.  There it is fence-accounting only; the
        sub-plans queue for this pump to move and commit."""
        pool = self.pool
        if not (self._inflight_plans or pool.pending_plans):
            return
        with self._span("compaction"):
            while self._inflight_plans:
                self._commit_plan(self._inflight_plans.pop(0))
            if not pool.pending_plans:
                return
            # the deficit is judged on *projected* free slabs: in-flight
            # reclamation is demand already being served, so the budget
            # only escalates when the pipeline itself falls behind
            budget = clean_budget(
                self.clean_budget, free_slabs=pool.projected_free_slabs(),
                trigger=pool.compact_trigger, blocks_per_slab=pool.S,
                queue_depth=len(self.queue) + len(self._resume))
            hot = self._hot_pages()
            moved = 0
            while pool.pending_plans and moved < budget:
                plan = pool.pending_plans.pop(0)
                self._move_plan(plan)
                moved += len(plan)
                if len(plan) and np.isin(plan.src_pages, hot).any():
                    # write hazard: commit through this plan, in order
                    while self._inflight_plans:
                        self._commit_plan(self._inflight_plans.pop(0))
                    self._commit_plan(plan)
                    # the commits remapped the tables — refresh the write
                    # set, or a chained later sub-plan (its source is an
                    # earlier sub-plan's destination, now live in the
                    # tables) would slip past the hazard check
                    hot = self._hot_pages()
                else:
                    self._inflight_plans.append(plan)

    def _drain_compaction(self) -> None:
        """Emergency synchronous drain (the pool's ``on_drain``): commit the
        whole pipeline FIFO.  Already-issued sub-plans only need their remap
        (pure host work — their moves are already ordered on device);
        unissued ones issue + commit like synchronous cleaning.  Called from
        the alloc path when capacity is needed *now*."""
        if not (self._inflight_plans or self.pool.pending_plans):
            return
        with self._span("compaction"):
            while self._inflight_plans:
                self._commit_plan(self._inflight_plans.pop(0))
            while self.pool.pending_plans:
                plan = self.pool.pending_plans.pop(0)
                self._move_plan(plan)
                self._commit_plan(plan)

    # ------------------------------------------------------------ integrity
    def audit(self) -> None:
        """Cross-check every reference holder against the pool's refcounts
        (engine debug mode; also run from tests and on the ``audit_every``
        cadence).  The invariant: each page's refcount equals the number of
        block-table rows holding it plus one if the prefix tree caches it —
        no leaks (refcount too high ⇒ pages never reclaimed, pool fills) and
        no double-frees (too low ⇒ a live page gets reallocated under a
        running sequence).  Also validates per-slot length/output ledgers
        and, when journaling, that the journal tail is durable and torn-free.
        """
        self.pool.check_invariants()
        if self.prefix_cache is not None:
            self.prefix_cache.check_invariants()
        expected = np.zeros_like(np.asarray(self.pool.block_ref))
        # across a pending async-compaction window the block tables and the
        # prefix tree still carry source ids (their remap lands with the
        # plan's commit), so every holder's pages are read through the
        # pool's pending-move LUT before the refcount cross-check
        for i in range(self.max_batch):
            if self.rid[i] >= 0:
                pages = self.pool.resolve(self.slot_pages(i).astype(np.int64))
                np.add.at(expected, pages, 1)
        if self.prefix_cache is not None:
            tree = self.prefix_cache.pages()
            if tree:
                np.add.at(expected,
                          self.pool.resolve(np.asarray(tree, np.int64)), 1)
        ref = np.asarray(self.pool.block_ref)
        assert (expected == ref).all(), \
            f"refcount mismatch at pages {np.flatnonzero(expected != ref)}"
        self._audit_fenced()
        for i in range(self.max_batch):
            if self.rid[i] >= 0 and not self._prefilling[i]:
                # lens counts prompt + consumed outputs (all emitted but the
                # last, which is the next decode input) — holds across
                # resume because a restart replays decode from the prompt
                assert self.lens[i] == (len(self._prompt[i])
                                        + self._out_n[i] - 1), \
                    f"slot {i}: lens ledger broken"
                assert self.to_gen[i] == len(self._out[i]) - self._out_n[i], \
                    f"slot {i}: to_gen ledger broken"
        if self.journal is not None:
            self.journal.check_tail()

    def _audit_fenced(self) -> None:
        """Fenced/in-flight cross-checks for async compaction (DESIGN.md
        §13): a FENCED slab is a victim whose evacuation is planned or
        issued but not committed — it must be invisible to allocation
        (never in a free list), unreachable from any holder (no resolved
        block-table or tree page lands in one), and exactly the home of
        every uncommitted plan's source pages (destinations are survivor
        placements into OPEN/USED slabs, never fenced ones)."""
        pool = self.pool
        core = pool.core
        fenced = np.flatnonzero(np.asarray(core.seg_state) == FENCED)
        plans = list(pool.pending_plans) + list(self._inflight_plans)
        if len(fenced) == 0 and not plans:
            assert pool.deferred_moves() == 0, "move debt with no plans"
            return
        assert not np.isin(np.asarray(core.free_list, np.int64),
                           fenced).any(), "fenced slab on the free list"
        S = pool.S
        for i in range(self.max_batch):
            if self.rid[i] >= 0:
                held = pool.resolve(self.slot_pages(i).astype(np.int64))
                assert not np.isin(held // S, fenced).any(), \
                    f"slot {i} holds a page in a fenced slab"
        if self.prefix_cache is not None and self.prefix_cache.n_pages:
            tree = pool.resolve(np.asarray(self.prefix_cache.pages(),
                                           np.int64))
            assert not np.isin(tree // S, fenced).any(), \
                "prefix tree holds a page in a fenced slab"
        for plan in plans:
            if len(plan) == 0:
                continue
            src = np.asarray(plan.src_pages, np.int64)
            dst = np.asarray(plan.dst_pages, np.int64)
            assert np.isin(src // S, fenced).all(), \
                "uncommitted plan source outside fenced slabs"
            assert not np.isin(dst // S, fenced).any(), \
                "uncommitted plan destination inside a fenced slab"

    def session_state(self) -> dict:
        """JSON-able snapshot of the *request-level* session state — what
        recovery restores (DESIGN.md §10).  Device state (K/V pages) is
        deliberately absent: decoded tokens are per-sequence deterministic,
        so live sequences re-prefill their prompt and re-decode their
        emitted span through the resume path instead of persisting pool
        tensors."""
        def entry(rid, prompt, max_new, out, out_n):
            return {"rid": int(rid), "prompt": [int(t) for t in prompt],
                    "max_new": int(max_new),
                    "out": ([int(t) for t in out[:out_n]]
                            if out is not None else [])}

        # a slot mid-replay (out_n < _jskip) knows more tokens than it has
        # re-decoded — snapshot the full journaled span, or a recovery from
        # this snapshot would lose the gap (post-snapshot emit records only
        # cover indices ≥ _jskip)
        live = sorted(
            (entry(self.rid[i], self._prompt[i],
                   int(self._out_n[i]) + int(self.to_gen[i]),
                   self._out[i],
                   int(max(self._out_n[i], self._jskip[i])))
             for i in np.flatnonzero(self.rid >= 0)),
            key=lambda e: e["rid"])
        return {
            "live": live,
            "resume": [entry(r.rid, r.prompt, r.max_new_tokens, r.out,
                             r.out_n) for r in self._resume],
            "queue": [entry(r.rid, r.prompt, r.max_new_tokens, r.out,
                            r.out_n) for r in self.queue],
            "finished": {str(k): v for k, v in self.finished.items()},
            "next_rid": self._next_rid,
            "predictor": {
                "kind": self.length_predictor.name,
                "value": getattr(self.length_predictor, "value", None),
                "n_obs": int(getattr(self.length_predictor, "n_obs", 0))},
            "counters": {
                "preemptions": self.preemptions, "resumes": self.resumes,
                "recomputed_tokens": self.recomputed_tokens,
                "dispatches": self.dispatches,
                "shed_count": self.shed_count,
                "prefill_chunks_dispatched": self.prefill_chunks_dispatched,
                "prefill_tokens_total": self._prefill_tokens_total,
                "prefill_tokens_saved": self._prefill_tokens_saved},
            "pool_stats": dataclasses.asdict(self.pool.stats),
            "u_now": float(self.pool.u_now),
            "prefix_tree": (self.prefix_cache.tree_state()
                            if self.prefix_cache is not None else []),
        }

    def snapshot(self) -> int:
        """Checkpoint the session through the manifest store and truncate
        the journal behind it (recovery = snapshot + bounded replay)."""
        from . import recovery  # deferred: recovery imports this module
        return recovery.snapshot(self)

    # ------------------------------------------------------------- metrics
    def metrics(self) -> dict:
        st = self.pool.stats
        m = {
            "blocks_written": st.blocks_written,
            "blocks_moved": st.blocks_moved,
            "wamp": st.wamp(),
            "mean_E_compacted": st.mean_E(),
            "compactions": st.compactions,
            "streams": self.streams,
            "stream_writes": list(st.stream_writes),
            "stream_moves": list(st.stream_moves),
            "per_stream_wamp": st.per_stream_wamp(),
            "free_blocks": self.pool.free_blocks(),
            # async-cleaning debt: moves planned but not yet committed plus
            # the slabs those moves will hand back (0 when synchronous)
            "compaction_debt_moves": self.pool.deferred_moves(),
            "fenced_slabs": self.pool.core.fenced_count(),
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "recomputed_tokens": self.recomputed_tokens,
            "dispatches": self.dispatches,
            "attn_pages_read": self.attn_pages_read,
            "attn_pages_table": self.attn_pages_table,
        }
        if self.shed_queue_depth:
            m["shed_count"] = self.shed_count
        if self.injector is not None:
            m["fault_retries"] = self.fault_retries_done
            m["fault_unwinds"] = self.fault_unwinds
        if self.journal is not None:
            js = self.journal.core.stats
            m["journal_records"] = self.journal.next_seq
            m["journal_bytes"] = js.user_bytes
            m["journal_wamp"] = js.wamp()   # stays 0: truncation moves nothing
        if self.recovery is not None:
            m["recovery"] = dict(self.recovery)
        if self.prefill_chunk:
            m["prefill_chunks_dispatched"] = self.prefill_chunks_dispatched
        if self.prefix_cache is not None:
            total = self._prefill_tokens_total
            saved = self._prefill_tokens_saved
            m.update(
                prefix_hit_rate=self.prefix_cache.hit_rate(),
                prefill_tokens=total,
                prefill_tokens_saved=saved,
                prefill_tokens_computed=total - saved,
                prefix_cache_pages=self.prefix_cache.n_pages,
                prefix_evictions=self.prefix_cache.evictions,
                frames_shared=st.frames_shared,
            )
        if self.calibration is not None:
            m["misroute_rate"] = self.calibration.misroute_rate()
        return m

    def _sample_metrics(self) -> None:
        """One metrics-logger row: the cumulative :meth:`metrics` dict plus
        point-in-time gauges (JSONL sink, ``metrics_every`` cadence)."""
        m = self.metrics()
        m.pop("recovery", None)   # nested dict, not a time series
        m["u_now"] = float(self.pool.u_now)
        m["queue_depth"] = len(self.queue) + len(self._resume)
        m["active_slots"] = int((self.rid >= 0).sum())
        self._metrics_logger.sample(m)

    def phase_report(self) -> dict:
        """Aggregate the per-dispatch phase splits (``phase_log=True`` or a
        tracer attached): per-phase means, the dispatch-latency p50/p99, and
        compaction's share of the p99 tail — the attribution the async-
        compaction work needs as its "before" evidence.

        Phases can nest (a compaction fires *inside* the admit/alloc path
        when allocation trips the pool's trigger), so per-phase tail shares
        may overlap and sum past 1.0 — each answers "what fraction of the
        tail's wall time had this phase running", not a partition."""
        rows = list(self.dispatch_phases)
        if not rows:
            # zeroed but *full-key* report: dashboards and bench gates index
            # these fields unconditionally, so an empty or not-yet-warm
            # window must not KeyError downstream
            return {"dispatches": 0, "p50_ms": 0.0, "p99_ms": 0.0,
                    "phase_mean_ms": {}, "phase_share_p99_tail": {},
                    "compaction_share_p99": 0.0,
                    "compaction_share_total": 0.0}
        tot = np.array([r["total"] for r in rows])
        p50, p99 = np.quantile(tot, [0.5, 0.99])
        tail = [r for r in rows if r["total"] >= p99]
        tail_tot = sum(r["total"] for r in tail)
        keys = sorted({k for r in rows for k in r} - {"total"})
        return {
            "dispatches": len(rows),
            "p50_ms": float(p50) * 1e3,
            "p99_ms": float(p99) * 1e3,
            "phase_mean_ms": {
                k: float(np.mean([r.get(k, 0.0) for r in rows])) * 1e3
                for k in keys},
            "phase_share_p99_tail": {
                k: (sum(r.get(k, 0.0) for r in tail) / tail_tot
                    if tail_tot else 0.0)
                for k in keys},
            "compaction_share_p99": (
                sum(r.get("compaction", 0.0) for r in tail) / tail_tot
                if tail_tot else 0.0),
            "compaction_share_total": float(
                sum(r.get("compaction", 0.0) for r in rows) / tot.sum())
            if tot.sum() else 0.0,
        }


def _prefill_cont_fn(params, k_pools, v_pools, pages, toks, true_len, *,
                     cfg, max_len, kv_len=None,
                     cache_dtype=jnp.bfloat16):
    """Prefix-hit prefill: gather the cached prefix K/V from the pool pages
    and run the tail-only continuation prefill (tfm.prefill_with_prefix).

    ``pages`` (n_shared,) are global physical page ids — replicated under a
    mesh, so the gather keeps the pools' head sharding and the hit path is
    mesh-oblivious like every other pool plan.  The prefix stays
    exact-length (no padding between prefix and tail), which is what makes
    the continuation arithmetic match a cold prefill row-for-row; the
    compile key is therefore (n_shared, tail bucket)."""
    k_pre = _from_pages(k_pools[:, pages])
    v_pre = _from_pages(v_pools[:, pages])
    logits, ks, vs = tfm.prefill_with_prefix(
        params, toks, cfg, k_pre, v_pre, max_len, true_len=true_len,
        kv_len=kv_len, cache_dtype=cache_dtype, gather_heads=True)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return first, ks, vs


def _prefill_fn(params, toks, true_len, *, cfg, max_len,
                cache_dtype=jnp.bfloat16):
    """Bucketed dense prefill; ``toks`` is right-padded to the bucket and
    ``true_len`` (traced) marks the prompt end.  Returns (first token,
    K (L, B, max_len, Kh, hd), V).  ``gather_heads`` keeps sharded prefill
    bit-identical under a serving mesh (and is inert off-mesh)."""
    logits, cache = tfm.prefill(params, toks, cfg, max_len, true_len=true_len,
                                cache_dtype=cache_dtype, gather_heads=True)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return first, cache["k"], cache["v"]
