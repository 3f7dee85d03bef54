"""Log-structured paged KV cache with MDC compaction (the paper on a pod).

Mapping (DESIGN.md §2): KV *block* = paper page; HBM *slab* (a group of
``blocks_per_slab`` contiguous pool pages) = paper segment; a block *dies*
when its sequence completes or is preempted (the paper's overwrite); the
clock ``u_now`` ticks once per block death (paper: once per update);
*compaction* evacuates the live blocks of victim slabs into fresh slabs and
rewrites the block tables (paper: cleaning).  Victim choice is the paper's
§5.1.3 MDC key over per-slab {A, C, u_p2} — identical code to the simulator
(repro.core.policies), with ``age``/``greedy``/``cost_benefit`` selectable
for ablation.

Why compaction at all (HBM has no erase blocks): continuous batching admits
a sequence only if *contiguous slab* capacity exists for its prompt growth;
after a mix of short/long sequences dies, free blocks are checkerboarded
across slabs exactly like Figure 1 of the paper.  Evacuating nearly-empty
slabs restores whole-slab free extents at the smallest possible copy cost —
and every copied byte is HBM read+write bandwidth stolen from decode, so
``Wamp`` prices lost decode throughput directly.

Placement (the paper's §5.3 sort-buffer, generalized to SepBIT death
streams): blocks are appended to one of ``streams`` open slabs bucketed by
*expected death time* (the serving analogue of u_p2: death ≈ now +
tokens-left-to-generate, from the scheduler's EWMA length predictor).
Blocks that will die together land in the same slab, so slabs die
nearly-whole — the mechanism by which MDC's hot/cold separation
materializes in a KV pool.  Compaction survivors re-route by the same
quantiles: unlike an update-driven store, a KV block's ``est_death`` is an
absolute clock, so surviving a clean carries no lifetime information and
SepBIT's survivor demotion is opt-in (``demote_survivors=True``, applied
only to *overdue* survivors — blocks alive past their predicted death,
where the misrouting is proven).  The routing machinery itself
lives in the core (:meth:`FrameLog.place` + :class:`StreamSet`), shared
with the simulator and the checkpoint store; this class supplies only the
hints.

All slab bookkeeping (free list, fill, seal, {A, C, u_p2}, eviction) lives
in the shared :class:`repro.core.logstructure.FrameLog` substrate — this
class owns only the serving *policy*: lifetime bucketing, the batched alloc
surface, and the compaction plan (src page -> dst page) the engine executes
with the ``segment_compact`` kernel.  The alloc and compaction paths are
batched and vectorized: cost is O(slabs touched), not O(blocks).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import numpy as np

from ..core.logstructure import FENCED, USED, FrameLog, Placement, StoreStats
from ..obs import span

NO_PAGE = -1

# the paper's oracle policies need per-page true update probabilities, which
# a serving pool cannot know (a block's owner gives no death distribution)
_SUPPORTED_POLICIES = ("mdc", "greedy", "age", "cost_benefit")

PoolStats = StoreStats  # unified counters; serving names are alias properties


@dataclasses.dataclass
class CompactionPlan:
    """src/dst physical page ids (parallel arrays) + owners for remapping.

    Page ids are *global* physical ids, so one plan is valid for every shard
    of a tensor-parallel pool: each shard applies the same src→dst moves to
    its head-slice of the pages (DESIGN.md §6).  Plans therefore carry no
    device or shard information — they are pure host-side placement.
    """
    src_pages: np.ndarray
    dst_pages: np.ndarray
    owners: np.ndarray
    # async cleaning (DESIGN.md §13): victim slabs whose *last* move this
    # sub-plan carries — released (FENCED → FREE) when the sub-plan commits.
    # None for synchronous plans, whose victims were released at evacuation.
    commit_segs: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.src_pages)

    def split(self, budget: int, segs: np.ndarray) -> list["CompactionPlan"]:
        """Cut one cleaning cycle into budget-sized incremental sub-plans.

        ``segs`` is the source slab per move (victim order — contiguous
        runs, the order :meth:`FrameLog.evacuate` emits).  Each victim
        slab's release rides with the sub-plan holding its last move, so a
        slab stays fenced exactly until every move out of it has
        committed.  ``budget <= 0`` means unmetered (one sub-plan)."""
        n = len(self)
        step = n if budget <= 0 else max(int(budget), 1)
        segs = np.asarray(segs, dtype=np.int64)
        last = {int(s): i for i, s in enumerate(segs)}
        plans = []
        for lo in range(0, n, step):
            hi = min(lo + step, n)
            # victims whose last move index falls inside [lo, hi)
            commit = np.array(sorted(s for s, i in last.items()
                                     if lo <= i < hi), dtype=np.int64)
            plans.append(CompactionPlan(
                src_pages=self.src_pages[lo:hi],
                dst_pages=self.dst_pages[lo:hi],
                owners=self.owners[lo:hi], commit_segs=commit))
        return plans

    def padded(self, bucket: int, fill: int) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) int32 arrays padded to ``bucket`` with fill→fill moves
        (the engine points ``fill`` at its trash page), so plan sizes share
        compiled executables."""
        src = np.full(bucket, fill, np.int32)
        dst = np.full(bucket, fill, np.int32)
        src[:len(self)] = self.src_pages
        dst[:len(self)] = self.dst_pages
        return src, dst


def _cleaning_cycle(fn):
    """Run ``fn``, one cleaning cycle, inside a ``pool.compact`` span:
    victim selection, evacuation accounting and survivor placement, with
    the engine's move and remap inside it where they run synchronously.
    The span's args are the slabs the cycle cleaned and the pages it
    moved, read off the store's counters."""
    @functools.wraps(fn)
    def cycle(self, *args, **kw):
        st = self.stats
        v0, m0 = st.cleaned_segments, st.gc_moves
        with span("compact", "pool", self.core.tracer) as late:
            try:
                return fn(self, *args, **kw)
            finally:
                late.update(victims=st.cleaned_segments - v0,
                            moves=st.gc_moves - m0)
    return cycle


class LogStructuredKVPool:
    """Block manager for a paged KV pool laid out as slabs of blocks.

    Physical pool page ids are ``slab * blocks_per_slab + slot``.  The tensor
    pool itself (k/v arrays indexed by page id) lives with the engine; this
    class owns allocation, death, victim selection and the compaction *plan*
    (src page -> dst page), which the engine executes with the
    ``segment_compact`` kernel before rewriting block tables.
    """

    def __init__(self, n_slabs: int, blocks_per_slab: int, *,
                 policy: str = "mdc", streams: int | None = None,
                 n_open: int | None = None, demote_survivors: bool = False,
                 compact_trigger: int = 2, compact_batch: int = 4,
                 horizon: float = 1e9):
        if policy not in _SUPPORTED_POLICIES:
            raise ValueError(
                f"KV pool cannot run policy {policy!r}: oracle policies "
                f"(mdc_opt) need true per-page update probabilities, which a "
                f"serving pool does not have; supported: {_SUPPORTED_POLICIES}")
        if n_open is not None:
            warnings.warn("n_open= is deprecated; use streams=",
                          DeprecationWarning, stacklevel=2)
        if streams is None:
            streams = 4 if n_open is None else n_open  # n_open: legacy alias
        self.n_slabs = n_slabs
        self.S = blocks_per_slab
        self.policy = policy
        self.n_open = streams
        self.demote_survivors = demote_survivors
        self.compact_trigger = compact_trigger
        self.compact_batch = compact_batch
        self.horizon = horizon

        # stream_sample="live": the death-quantile cuts come from the live
        # blocks' death estimates (the pool can enumerate them), not the
        # recent-append ring — placement tracks the population that is
        # actually resident.
        self.core = FrameLog(n_slabs, blocks_per_slab,
                             auto_release_empty=True, n_streams=streams,
                             stream_sample="live", stream_horizon=horizon)
        self.core._oom_msg = "KV pool out of slabs (compaction failed)"
        self.core._noroom_msg = "KV pool: no open slab (all slabs sealed+full)"
        # Flat per-page views of the core's slot arrays (page = slab*S + slot):
        # the owner sequence id (-1 dead/empty), the estimated death clock,
        # and the reference count (shared prefix pages hold one per
        # referencing sequence plus one for the prefix cache itself).
        self.block_owner = self.core.slot_item.reshape(-1)
        self.block_death = self.core.slot_up2.reshape(-1)
        self.block_ref = self.core.slot_ref.reshape(-1)

        # Plan executor: the engine registers a callback that performs the
        # tensor move (kernels.segment_compact) + block-table remap.  It MUST
        # run before any page id freed by the plan can be re-allocated, so
        # the pool invokes it synchronously at plan creation.
        self.on_compaction = None  # Callable[[CompactionPlan], None] | None
        # manual mode (no callback): plans queue here; the caller must drain
        # them before its next alloc.  Async mode (DESIGN.md §13) reuses the
        # queue: plan_compaction() appends fenced sub-plans, the engine's
        # pump issues + commits them across dispatches.
        self.pending_plans: list[CompactionPlan] = []
        # pressure hook: called with the page deficit when compaction alone
        # cannot satisfy an alloc — the engine registers the prefix cache's
        # LRU eviction here, so unreferenced cached prefixes are given back
        # before the pool declares OOM
        self.on_pressure = None  # Callable[[int], None] | None
        # async-cleaning drain hook: called (no args) when the alloc path
        # needs capacity that only committing the planned/in-flight pipeline
        # can provide — the engine drains FIFO (issue + remap + commit)
        self.on_drain = None  # Callable[[], None] | None
        # sub-plan grain for alloc-path fence-planning (0 = monolithic);
        # the engine sets this to its per-dispatch clean budget
        self.plan_budget = 0
        # pending-move LUT: between plan and commit, external holders (block
        # tables, the prefix tree) still carry *source* page ids while the
        # accounting (owner/death/refcount) lives at the destination.
        # resolve() translates; identity (+trash passthrough) when no debt.
        self._remap = np.arange(n_slabs * blocks_per_slab + 1, dtype=np.int64)
        self._pending_moves = 0

    # unified accounting lives in the core
    @property
    def stats(self) -> StoreStats:
        return self.core.stats

    @property
    def u_now(self) -> float:
        return self.core.u_now

    @property
    def free_slabs(self) -> list[int]:
        return self.core.free_list

    # -- observability (repro.obs) -------------------------------------------
    def attach_tracer(self, tracer) -> None:
        """Stream segment-lifecycle events (seg.open/seal/evacuate/clean)
        to ``tracer`` from the shared core; None detaches."""
        self.core.tracer = tracer

    def enable_calibration(self, cal) -> None:
        """Route block deaths to a :class:`repro.obs.DeathCalibration` —
        each block's est-death (the absolute clock it was placed with) is
        compared against ``u_now`` when it actually dies."""
        self.core.enable_calibration(cal)

    # ------------------------------------------------------------ allocation
    def free_blocks(self) -> int:
        return self.core.free_frames()

    def deferred_moves(self) -> int:
        """Blocks whose move is planned/in-flight but not committed."""
        return self._pending_moves

    def projected_free_slabs(self) -> int:
        """Free slabs counting fenced victims as already reclaimed — what
        the free count becomes once the planned pipeline commits.  The
        async pump plans against this so it stops planning once enough
        reclamation is in flight, instead of victimizing the whole pool."""
        return self.core.free_count() + self.core.fenced_count()

    def resolve(self, pages: np.ndarray) -> np.ndarray:
        """Translate page ids through the pending-move LUT (DESIGN.md §13).

        Between ``plan_compaction`` and ``commit_plan`` the block tables and
        the prefix tree still hold *source* ids (their remap is deferred to
        the engine's next sync point) while the pool's accounting rows moved
        to the destinations.  Every accounting entry point resolves first;
        with no debt this is the identity."""
        pages = np.asarray(pages, dtype=np.int64)
        if self._pending_moves == 0:
            return pages
        return self._remap[pages]

    def admission_reserve(self) -> int:
        """Blocks admission control must leave free: the compaction reserve.

        ``compact_trigger`` is a *slab* count (``_compact_until`` compares it
        to ``core.free_count()``, the free-slab count), so the block-unit
        headroom admission has to respect is ``compact_trigger * S`` —
        admitting into this reserve both starves the cleaner of evacuation
        destinations and leaves no cushion for in-flight page growth of the
        already-admitted sequences."""
        return self.compact_trigger * self.S

    # open slabs + quantile cuts live in the core's StreamSet; legacy views:
    @property
    def _open(self) -> np.ndarray:
        return self.core.streams.open

    @property
    def _open_bounds(self) -> np.ndarray:
        return self.core.streams.bounds

    def _place(self, owners: np.ndarray, deaths: np.ndarray,
               kind: str, refs: np.ndarray | None = None) -> np.ndarray:
        """Deprecated shim: route + append via the core's unified placement."""
        return self.core.place(owners, Placement(est_death=deaths, kind=kind,
                                                 refs=refs))

    def alloc_blocks(self, seq_ids: np.ndarray,
                     est_deaths) -> np.ndarray:
        """Allocate one pool page per entry; returns physical page ids.

        ``est_deaths``: a :class:`Placement` hint, or (deprecated shim) a bare
        array of estimated clock values at which each block will die (now +
        expected remaining tokens of its sequence).  Drives the SepBIT
        death-stream placement: similar-death blocks share a slab.
        Compaction fires *before* placement when free slabs run low, so page
        ids handed out by one call are never moved by that same call.
        """
        seq_ids = np.asarray(seq_ids, dtype=np.int64)
        if isinstance(est_deaths, Placement):
            p = est_deaths
            if p.kind != "user":
                p = dataclasses.replace(p, kind="user")
        else:
            p = Placement(est_death=np.asarray(est_deaths, dtype=np.float64),
                          kind="user")
        n = len(seq_ids)
        if n == 0:
            return np.empty(0, dtype=np.int64)
        self._compact_until(n)
        if self.core.free_frames() < n and self.on_pressure is not None:
            # last resort before OOM: ask the owner to drop reclaimable
            # references (prefix-cache LRU eviction), then clean again
            self.on_pressure(n - self.core.free_frames())
            self._compact_until(n)
        if self.core.free_frames() < n:
            raise RuntimeError("KV pool out of slabs (compaction failed)")
        return self.core.place(seq_ids, p)

    def _compact_until(self, n: int) -> None:
        """Run compaction cycles until ``n`` frames are appendable and the
        free-slab reserve is above the trigger, or no cycle makes progress.

        With async cleaning active, the reserve trigger is judged on
        *projected* free slabs (actual + fenced): in-flight reclamation
        counts, so a healthy pipeline lets allocation dig into the actual
        reserve without forcing cleaning back into the alloc path — that
        deferral is the whole point of the refactor.

        When the reserve does cross the trigger here, the crossing is
        almost always *reserve maintenance*, not an actual frame shortage:
        the victim slabs that make a cycle worthwhile were typically sealed
        by this very admission wave, so no step-boundary planner can have
        seen them.  In that case the alloc path **fence-plans** instead of
        compacting: :meth:`plan_compaction` is pure host accounting (the
        survivors fit in the room we already have), the victims fence, the
        projected reserve refills, and the data moves defer to the engine's
        pump — budget-spread across subsequent dispatches.  Victim
        selection happens at exactly the state synchronous cleaning would
        have used, so write amplification is unchanged.

        Only when frames are genuinely short does the alloc path drain the
        pipeline (``on_drain``): committing it releases the fenced victim
        slabs — already-issued moves just need their remap, which is pure
        host work — before any new synchronous cycle is paid here.
        Without async cleaning there is never fenced debt, so projected ==
        actual and the behavior is the classic synchronous trigger."""
        while (self.projected_free_slabs() <= self.compact_trigger
               or self.core.free_frames() < n):
            if self.on_drain is not None and self.core.free_frames() >= n:
                # reserve maintenance, not shortage: fence-plan and return
                # the moves to the pump.  Guard on projected progress —
                # placement can consume free slabs for fresh open segments,
                # so a cycle that does not raise the projection falls
                # through to the synchronous path below.
                proj = self.projected_free_slabs()
                if (self.plan_compaction(self.plan_budget)
                        and self.projected_free_slabs() > proj):
                    continue
            before = self.core.free_frames()
            if self.on_drain is not None and self.deferred_moves():
                debt = self.deferred_moves()
                self.on_drain()
                if self.deferred_moves() < debt:
                    continue
            if self.compact() is None or self.core.free_frames() <= before:
                break

    def alloc_block(self, seq_id: int, est_death: float) -> int:
        """Single-block convenience wrapper over :meth:`alloc_blocks`."""
        return int(self.alloc_blocks(np.array([seq_id]),
                                     np.array([est_death]))[0])

    # ------------------------------------------------------------- sharing
    def incref_pages(self, pages: np.ndarray,
                     est_deaths: np.ndarray | float | None = None) -> None:
        """Add one reference per page (a sequence or the prefix cache starts
        sharing them).  ``est_deaths`` raises each page's death estimate to
        the max over its referencing sequences — shared hot prefixes sort
        into long-lifetime slabs and stop being pointlessly relocated."""
        pages = self.resolve(pages)
        if len(pages) == 0:
            return
        assert (self.block_owner[pages] >= 0).all(), "incref of dead page"
        up2 = None
        if est_deaths is not None:
            up2 = np.broadcast_to(np.asarray(est_deaths, np.float64),
                                  pages.shape)
        self.core.incref_slots(pages // self.S, pages % self.S, up2=up2)

    # --------------------------------------------------------------- death
    def free_pages(self, pages: np.ndarray) -> None:
        """Drop one reference per block; unshared blocks die (their sequence
        finished / was preempted), shared ones stay live for the remaining
        referencers — a page is freed exactly when its refcount hits zero."""
        pages = np.asarray(pages, dtype=np.int64)
        pages = self.resolve(pages[pages >= 0])
        if len(pages) == 0:
            return
        assert (self.block_owner[pages] >= 0).all(), "double free"
        # sealed slabs that become fully dead are reclaimed for free by the
        # core (auto_release_empty); open slabs stay open (append-only slots)
        self.core.kill_slots(pages // self.S, pages % self.S, tick=True)

    # ----------------------------------------------------------- compaction
    def select_victims(self, k: int | None = None) -> np.ndarray:
        eligible = (self.core.seg_state == USED) & (self.core.seg_live < self.S)
        return self.core.select_victims(self.policy, k or self.compact_batch,
                                        eligible=eligible)

    def maybe_compact(self):
        """Compact if free space is low.  Returns a plan or None.

        The caller (engine) must execute the returned plan on the tensor pool
        (kernels.segment_compact) and remap its block tables.
        """
        if self.core.free_count() > self.compact_trigger:
            return None
        return self.compact()

    @_cleaning_cycle
    def compact(self):
        """Evacuate victims; returns CompactionPlan(src_pages, dst_pages).

        Synchronous cleaning: victims are released at evacuation and the
        plan executes (or queues) immediately.  Never interleaves with
        uncommitted async plans — the pipeline is drained first, so the
        block tables are current when this plan's remap applies."""
        if self.deferred_moves() and self.on_drain is not None:
            self.on_drain()
        assert self.deferred_moves() == 0, \
            "synchronous compact with uncommitted async plans"
        victims = self.select_victims()
        if len(victims) == 0:
            return None
        res = self.core.evacuate(victims)
        src = res.segs * self.S + res.slots
        # §5.3: sort survivors by expected death so they re-cluster; the
        # victims were freed above, so capacity for the survivors exists.
        # Reference counts ride along: sharing is invariant under relocation.
        # SepBIT survivor inference, restricted to *overdue* blocks: a
        # block still alive past its predicted death was provably routed
        # too hot — demote one stream.  Blocks whose predicted death is
        # still ahead learned nothing by surviving (KV deaths are absolute
        # clocks, not recency guesses), so they re-route by quantile.
        order = np.argsort(res.up2_slot, kind="stable")
        streams = (self.core.demote_streams(res.streams, res.up2_slot,
                                            overdue=res.up2_slot <= self.u_now)
                   if self.demote_survivors else None)
        dst = np.empty(len(src), dtype=np.int64)
        dst[order] = self.core.place(
            res.items[order],
            Placement(est_death=res.up2_slot[order],
                      stream=None if streams is None else streams[order],
                      kind="gc", refs=res.refs[order]))
        plan = CompactionPlan(src_pages=src, dst_pages=dst, owners=res.items)
        if self.on_compaction is not None:
            self.on_compaction(plan)
        else:
            self.pending_plans.append(plan)
        return plan

    # --------------------------------------------- async two-phase cleaning
    @_cleaning_cycle
    def plan_compaction(self, budget: int = 0) -> list:
        """Phase one of async cleaning (DESIGN.md §13): one cleaning cycle
        whose victims are *fenced* instead of freed, cut into budget-sized
        sub-plans appended to ``pending_plans``.

        Survivors are placed (and all Wamp accounting lands) now, exactly
        like :meth:`compact`; only the device move and the block-table
        remap are deferred.  The victim slabs stay FENCED — not
        allocatable, not re-victimizable (``select_victims`` needs USED) —
        until :meth:`commit_plan` releases them, because until the remap
        both the deferred move and stale external ids still read them.
        Returns the new sub-plans ([] when no victim fits: fenced planning
        must pay survivor placement out of *current* free room, so under
        extreme pressure the caller falls back to the synchronous path)."""
        victims = self.select_victims()
        if len(victims) == 0:
            return []
        # capacity fence: survivors consume appendable room now but the
        # victims only return at commit — keep victims (ranked best-first)
        # whose cumulative survivor count fits
        fits = self.core.seg_live[victims].cumsum() <= self.core.free_frames()
        victims = victims[fits]
        if len(victims) == 0:
            return []
        res = self.core.evacuate(victims, fence=True)
        if len(res) == 0:
            # nothing live to move: the cycle is pure reclamation
            self.core.commit_fenced(victims)
            return []
        src = res.segs * self.S + res.slots
        order = np.argsort(res.up2_slot, kind="stable")
        streams = (self.core.demote_streams(res.streams, res.up2_slot,
                                            overdue=res.up2_slot <= self.u_now)
                   if self.demote_survivors else None)
        dst = np.empty(len(src), dtype=np.int64)
        dst[order] = self.core.place(
            res.items[order],
            Placement(est_death=res.up2_slot[order],
                      stream=None if streams is None else streams[order],
                      kind="gc", refs=res.refs[order]))
        # victims that contributed no move (fully-dead slabs) reclaim now
        empty = victims[~np.isin(victims, res.segs)]
        if len(empty):
            self.core.commit_fenced(empty)
        # compose into the pending LUT: a stale id whose earlier destination
        # is itself being moved now resolves through to the newest location
        m = np.arange(len(self._remap), dtype=np.int64)
        m[src] = dst
        self._remap = m[self._remap]
        self._pending_moves += len(src)
        plans = CompactionPlan(src, dst, res.items).split(budget, res.segs)
        self.pending_plans.extend(plans)
        return plans

    def commit_plan(self, plan: CompactionPlan) -> None:
        """Phase two: the owner applied this sub-plan's LUT remap to every
        external holder (block tables + prefix tree), so the source ids are
        gone — retire the pending-LUT entries and release the victim slabs
        whose last move this sub-plan carried.  Sub-plans MUST commit in
        plan order (the pending LUT composes FIFO)."""
        if len(plan):
            self._remap[plan.src_pages] = plan.src_pages
            self._pending_moves -= len(plan)
            self.stats.gc_committed += len(plan)
        if plan.commit_segs is not None and len(plan.commit_segs):
            self.core.commit_fenced(plan.commit_segs)

    # ------------------------------------------------------------ invariants
    def check_invariants(self) -> None:
        self.core.check_invariants()  # includes the stream/open-slab checks
        # pending-move LUT: every non-identity entry maps a page in a FENCED
        # slab to a live destination; with no debt the LUT is the identity
        ident = np.arange(len(self._remap) - 1, dtype=np.int64)
        stale = np.flatnonzero(self._remap[:-1] != ident)
        assert self._pending_moves >= 0, "negative deferred-move debt"
        if self._pending_moves == 0:
            assert len(stale) == 0, "pending LUT left behind after commit"
        else:
            # (destinations may legitimately die before commit — a moved
            # block's owner can finish inside the window — so only the
            # source side is asserted here)
            assert (self.core.seg_state[stale // self.S] == FENCED).all(), \
                "pending-move source outside a fenced slab"
