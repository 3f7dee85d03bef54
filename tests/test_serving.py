"""Serving-pool + engine tests.

The crucial equivalence: decoding through the paged, MDC-compacted pool must
produce *exactly* the tokens the dense-cache decode path produces — i.e. the
paper's cleaning is invisible to the model (pure space management), no matter
how often slabs are evacuated and block tables rewritten.
"""

import collections

import numpy as np
import pytest
from _hyp import given, settings, st  # degrades to skips without hypothesis

from repro.configs import get_config
from repro.models import Model
from repro.serving import LogStructuredKVPool, PagedServingEngine


# ----------------------------------------------------------------- pool unit

def test_pool_alloc_seal_free_cycle():
    pool = LogStructuredKVPool(8, 4, policy="mdc", compact_trigger=1,
                               compact_batch=2, n_open=2)
    pages = [pool.alloc_block(seq_id=1, est_death=10.0) for _ in range(8)]
    assert len(set(pages)) == 8
    pool.check_invariants()
    pool.free_pages(np.asarray(pages))
    pool.check_invariants()
    assert pool.stats.blocks_died == 8


def test_pool_compaction_reclaims_checkerboard():
    """Interleave two lifetime classes, kill one: slabs checkerboard; MDC
    compaction must recover whole free slabs by moving only live blocks."""
    pool = LogStructuredKVPool(8, 4, policy="mdc", compact_trigger=0,
                               compact_batch=4, n_open=1)
    long_pages, short_pages = [], []
    for i in range(12):
        short_pages.append(pool.alloc_block(100 + i, est_death=5.0))
        long_pages.append(pool.alloc_block(200 + i, est_death=1e6))
    pool.free_pages(np.asarray(short_pages))
    pool.check_invariants()
    free_before = len(pool.free_slabs)
    plan = pool.compact()
    assert plan is not None and len(plan) > 0
    pool.check_invariants()
    assert len(pool.free_slabs) > free_before
    # moved blocks kept their owners
    assert (pool.block_owner[plan.dst_pages] >= 200).all()
    # victims' frames were actually the short-lived checkerboard
    assert pool.stats.blocks_moved == len(plan)


def test_pool_batched_alloc_matches_singles():
    """alloc_blocks is the hot-path API: one call must behave like the loop
    of alloc_block calls (same count, unique pages, correct owners/deaths)."""
    pool = LogStructuredKVPool(8, 4, policy="mdc", compact_trigger=1,
                               compact_batch=2, n_open=2)
    seq_ids = np.array([7, 7, 7, 9, 9, 11])
    deaths = np.array([50.0, 50.0, 50.0, 9.0, 9.0, 1e6])
    pages = pool.alloc_blocks(seq_ids, deaths)
    assert len(np.unique(pages)) == 6
    assert (pool.block_owner[pages] == seq_ids).all()
    assert (pool.block_death[pages] == deaths).all()
    assert pool.stats.blocks_written == 6
    pool.check_invariants()
    pool.free_pages(pages)
    pool.check_invariants()
    assert pool.stats.blocks_died == 6
    assert (pool.block_owner[pages] == -1).all()


def test_pool_rejects_oracle_policy():
    """The pool has no true update probabilities: mdc_opt must fail loudly
    instead of silently degenerating on seg_prob == 0."""
    with pytest.raises(ValueError, match="mdc_opt"):
        LogStructuredKVPool(8, 4, policy="mdc_opt")


@given(st.integers(0, 1000), st.sampled_from(["mdc", "greedy", "age",
                                              "cost_benefit"]))
@settings(max_examples=10, deadline=None)
def test_pool_invariants_random_traffic(seed, policy):
    rng = np.random.default_rng(seed)
    pool = LogStructuredKVPool(10, 4, policy=policy, compact_trigger=2,
                               compact_batch=3, n_open=2)
    live: dict[int, list[int]] = {}

    def execute(plan):  # the engine contract: remap held ids synchronously
        remap = dict(zip(plan.src_pages.tolist(), plan.dst_pages.tolist()))
        for k in live:
            live[k][:] = [remap.get(p, p) for p in live[k]]

    pool.on_compaction = execute
    sid = 0
    for _ in range(200):
        if rng.random() < 0.6 or not live:
            if pool.free_blocks() < 6:
                continue
            n = int(rng.integers(1, 4))
            pages = live.setdefault(sid, [])
            for _ in range(n):
                pages.append(pool.alloc_block(sid, float(rng.integers(1, 100))))
            sid += 1
        else:
            kill = rng.choice(list(live))
            pool.free_pages(np.asarray(live.pop(kill)))
        pool.check_invariants()


# ------------------------------------------------------------ engine end2end

@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("qwen3-1.7b").smoke()
    return Model(cfg)


def _dense_reference_decode(model, prompt, n_new):
    """Dense-cache greedy decode (the model's own serve path)."""
    import jax

    from repro.models import transformer as tfm
    params = model.init(jax.random.PRNGKey(0))
    return params, tfm.greedy_decode(params, prompt, model.cfg, n_new)


def test_paged_engine_matches_dense_decode(smoke_model):
    """Cleaning must be invisible: paged+compacted == dense decode, exactly."""
    prompt = np.arange(1, 21) % smoke_model.cfg.vocab_size
    n_new = 12
    params, want = _dense_reference_decode(smoke_model, prompt, n_new)
    # tiny pool + aggressive trigger ⇒ several compactions during the run
    eng = PagedServingEngine(smoke_model, n_slabs=12, blocks_per_slab=2,
                             page_T=8, max_batch=2, max_seq=64,
                             policy="mdc", params=params,
                             compact_trigger=2, compact_batch=3)
    rid = eng.submit(prompt, n_new)
    eng.run_to_completion()
    got = eng.finished[rid]
    assert got == want, (got, want)
    eng.pool.check_invariants()
    eng.audit()


def test_engine_continuous_batching_many_requests(smoke_model):
    """Mixed-length request stream; pool must stay consistent and all
    requests must finish with the right token counts."""
    rng = np.random.default_rng(0)
    eng = PagedServingEngine(smoke_model, n_slabs=14, blocks_per_slab=2,
                             page_T=8, max_batch=3, max_seq=96,
                             policy="mdc", compact_trigger=2, compact_batch=3)
    lens = [5, 17, 9, 24, 3, 12]
    news = [6, 10, 4, 8, 12, 5]
    rids = [eng.submit(rng.integers(1, 100, size=l), n)
            for l, n in zip(lens, news)]
    eng.run_to_completion()
    for rid, n in zip(rids, news):
        assert len(eng.finished[rid]) == n
    eng.pool.check_invariants()
    eng.audit()
    m = eng.metrics()
    assert m["blocks_written"] > 0
    assert m["free_blocks"] == eng.pool.n_slabs * eng.pool.S  # all freed


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ref", "pallas_interpret"])
def test_engine_compaction_plan_execution_consistent(smoke_model, use_pallas):
    """Run a tiny pool until compaction fires and assert, after every step,
    that block tables, pool ownership and the core invariants stay mutually
    consistent — on both the ref path and the pallas (interpret) path.  The
    decoded tokens must match the dense reference, which is the oracle that
    the *tensor* moves (kernels.segment_compact) followed the plan."""
    prompt = (np.arange(3, 30) * 5) % smoke_model.cfg.vocab_size
    n_new = 10
    params, want = _dense_reference_decode(smoke_model, prompt, n_new)
    eng = PagedServingEngine(smoke_model, n_slabs=7, blocks_per_slab=2,
                             page_T=8, max_batch=3, max_seq=96,
                             policy="mdc", params=params, n_open=1,
                             compact_trigger=2, compact_batch=3,
                             use_pallas=use_pallas)
    rid = eng.submit(prompt, n_new)
    rng = np.random.default_rng(1)
    side = [eng.submit(rng.integers(1, 100, size=l), n)
            for l, n in [(5, 8), (11, 6), (3, 12)]]
    for step in range(10_000):
        eng.step()
        if step % 3 == 2:
            # compaction is legal at any time; force extra cycles so the
            # plan-execution path runs many times, not just under pressure
            eng.pool.compact()
        eng.pool.check_invariants()
        for i in range(eng.max_batch):
            if not eng.slot_active(i):
                continue
            pages = eng.slot_pages(i)
            # block table rows beyond the held pages stay parked on trash
            assert (eng.bt[i, len(pages):] == eng.trash_page).all()
            # every held page is owned by this sequence in the pool
            assert (eng.pool.block_owner[pages] == eng.rid[i]).all()
        if not eng.has_work():
            break
    assert eng.metrics()["compactions"] >= 2, "config must force compactions"
    assert eng.finished[rid] == want
    for r, n in zip(side, [8, 6, 12]):
        assert len(eng.finished[r]) == n
    assert eng.metrics()["free_blocks"] == eng.pool.n_slabs * eng.pool.S


# -------------------------------------------------- multi-step decode loop

def _mixed_stream(eng, vocab, seed=3):
    rng = np.random.default_rng(seed)
    lens = [5, 17, 9, 24, 3, 12]
    news = [6, 10, 4, 8, 12, 5]
    return [eng.submit(rng.integers(1, vocab, size=l), n)
            for l, n in zip(lens, news)], news


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ref", "pallas_interpret"])
def test_multistep_decode_equals_singlestep(smoke_model, use_pallas):
    """The tentpole equivalence: a multi-token device dispatch must be an
    invisible batching of the single-token loop — bit-identical tokens and
    identical pool traffic (Wamp / compaction counters), because the event
    schedule (page-boundary allocs, deaths, compactions) is the same."""
    results = []
    for chunk in (1, 8):
        eng = PagedServingEngine(smoke_model, n_slabs=14, blocks_per_slab=2,
                                 page_T=8, max_batch=3, max_seq=96,
                                 policy="mdc", compact_trigger=2,
                                 compact_batch=3, seed=0,
                                 use_pallas=use_pallas,
                                 max_decode_chunk=chunk)
        rids, news = _mixed_stream(eng, smoke_model.cfg.vocab_size)
        eng.run_to_completion()
        eng.pool.check_invariants()
        for rid, n in zip(rids, news):
            assert len(eng.finished[rid]) == n
        results.append((eng.finished, eng.metrics()))
    (fin1, m1), (fin8, m8) = results
    assert fin1 == fin8                      # bit-identical tokens
    assert m1["wamp"] == m8["wamp"]          # identical pool traffic
    assert m1["compactions"] == m8["compactions"]
    assert m1["blocks_written"] == m8["blocks_written"]
    assert m1["blocks_moved"] == m8["blocks_moved"]


def test_compaction_midbatch_remaps_device_block_tables(smoke_model):
    """Compaction firing between multi-step dispatches must remap both the
    host block-table matrix and its device-resident mirror, and stay
    invisible to the decoded tokens (dense reference is the oracle)."""
    import jax.numpy as jnp

    prompt = (np.arange(3, 30) * 5) % smoke_model.cfg.vocab_size
    n_new = 10
    params, want = _dense_reference_decode(smoke_model, prompt, n_new)
    eng = PagedServingEngine(smoke_model, n_slabs=7, blocks_per_slab=2,
                             page_T=8, max_batch=3, max_seq=96,
                             policy="mdc", params=params, n_open=1,
                             compact_trigger=2, compact_batch=3,
                             max_decode_chunk=8)
    rid = eng.submit(prompt, n_new)
    rng = np.random.default_rng(1)
    side = [eng.submit(rng.integers(1, 100, size=l), n)
            for l, n in [(5, 8), (11, 6), (3, 12)]]
    compacted = 0
    for _ in range(10_000):
        eng.step()
        plan = eng.pool.compact()  # force mid-batch compaction every dispatch
        if plan is not None and len(plan):
            compacted += 1
            # host remap is a vectorized lookup: evacuated pages are gone
            # from bt (unless re-used as a destination in the same plan)
            held = eng.bt[eng.bt != eng.trash_page]
            gone = np.setdiff1d(plan.src_pages, plan.dst_pages)
            assert not np.isin(gone, held).any()
        eng._sync_device()
        # the device-resident block table mirrors the host matrix exactly
        assert (np.asarray(eng._bt_dev) == eng.bt).all()
        assert isinstance(eng._bt_dev, jnp.ndarray)
        if not eng.has_work():
            break
    assert compacted >= 1, "at least one forced mid-batch compaction"
    assert eng.metrics()["compactions"] >= 2, "config must force compactions"
    assert eng.finished[rid] == want
    for r, n in zip(side, [8, 6, 12]):
        assert len(eng.finished[r]) == n
    eng.pool.check_invariants()


def test_single_token_request_reported_by_step(smoke_model):
    """A request satisfied entirely by its prefill token (max_new_tokens=1)
    completes during admission; step() must still report its rid."""
    eng = PagedServingEngine(smoke_model, n_slabs=8, blocks_per_slab=2,
                             page_T=8, max_batch=2, max_seq=64, policy="mdc")
    rid = eng.submit(np.arange(1, 6), 1)
    done = eng.step()
    assert done == [rid]
    assert len(eng.finished[rid]) == 1
    assert not eng.has_work()
    eng.pool.check_invariants()


def test_non_pow2_page_size(smoke_model):
    """Prefill bucketing must not assume page_T is a power of two."""
    prompt = (np.arange(2, 16) * 3) % smoke_model.cfg.vocab_size
    eng = PagedServingEngine(smoke_model, n_slabs=10, blocks_per_slab=2,
                             page_T=12, max_batch=2, max_seq=96,
                             policy="mdc", compact_trigger=2, compact_batch=2)
    rid = eng.submit(prompt, 6)
    eng.run_to_completion()
    assert len(eng.finished[rid]) == 6
    eng.pool.check_invariants()


@pytest.mark.parametrize("pool_dtype,page_T", [("bfloat16", 8),
                                                ("float32", 4)])
def test_mosaic_pool_page_T_must_fill_sublane_tile(smoke_model, monkeypatch,
                                                    pool_dtype, page_T):
    """On a TPU with the Mosaic kernels, a page_T below the pool dtype's
    sublane tile (16 rows for bf16, 8 for f32) would pad every page of the
    pool; the engine refuses it instead."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="sublane tile"):
        PagedServingEngine(smoke_model, n_slabs=4, blocks_per_slab=2,
                           page_T=page_T, max_batch=2, max_seq=32,
                           use_pallas=True, pool_dtype=jnp.dtype(pool_dtype))


@pytest.mark.parametrize("argv,want", [
    ([], "CHIP_GEOMETRY"), (["--smoke"], "SMOKE_GEOMETRY"),
    (["--smoke", "--page-T", "16", "--max-seq", "512"], "override")])
def test_serve_cli_pool_geometry(monkeypatch, argv, want):
    """The CLI sizes the pool for the chip at published widths, for the
    CPU under --smoke, and explicit options win over either."""
    import sys

    import jax

    from repro.launch import serve
    seen = {}

    def fake_serve_run(**kw):
        seen.update(kw)
        return dict(policy=kw["policy"], wamp=0.0)

    monkeypatch.setattr(serve, "serve_run", fake_serve_run)
    monkeypatch.setattr(serve, "setup_compile_cache", lambda: "")
    monkeypatch.setattr(serve.Model, "init", lambda self, key: {})
    monkeypatch.setattr(jax, "jit", lambda f: f)
    monkeypatch.setattr(sys, "argv", ["serve", "--policies", "mdc", *argv])
    serve.main()
    geometry = {k: seen[k] for k in serve.CHIP_GEOMETRY}
    if want == "override":
        assert geometry == dict(serve.SMOKE_GEOMETRY, page_T=16, max_seq=512)
    else:
        assert geometry == getattr(serve, want)
    assert (seen["model"].cfg.n_layers == 2) == ("--smoke" in argv)


# ------------------------------------------------- stop-token decode (§8)

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ref", "pallas_interpret"])
def test_stop_token_early_exit_matches_dense(smoke_model, use_pallas):
    """Stop-token decode is detected on device inside the multi-token
    dispatch: the request must truncate at (and include) the first stop
    token, exactly like the dense stop-aware reference, and free its pool
    pages early."""
    from repro.models import transformer as tfm
    import jax

    prompt = np.arange(1, 21) % smoke_model.cfg.vocab_size
    params = smoke_model.init(jax.random.PRNGKey(0))
    full = tfm.greedy_decode(params, prompt, smoke_model.cfg, 12)
    stop = full[5]  # a token the stream actually emits, mid-output
    want = tfm.greedy_decode(params, prompt, smoke_model.cfg, 12,
                             stop_token=stop)
    assert want == full[:full.index(stop) + 1] and len(want) < len(full)
    eng = PagedServingEngine(smoke_model, n_slabs=12, blocks_per_slab=2,
                             page_T=8, max_batch=2, max_seq=64,
                             policy="mdc", params=params, compact_trigger=2,
                             compact_batch=3, use_pallas=use_pallas,
                             stop_token=stop)
    rid = eng.submit(prompt, 12)
    eng.run_to_completion()
    assert eng.finished[rid] == want
    eng.pool.check_invariants()
    assert eng.metrics()["free_blocks"] == eng.pool.n_slabs * eng.pool.S


def test_attn_page_counters_match_kernel_inputs(smoke_model, monkeypatch):
    """``attn_pages_read`` / ``attn_pages_table`` equal the pages and table
    entries of every slot of every decode iteration, summed from the
    lengths the paged-attention kernel is actually handed (a callback on
    its inputs at layer 0), under multi-token dispatches with a stop token
    that freezes slots mid-dispatch.  Inactive slots attend nothing."""
    import jax

    from repro.kernels import ops
    seen = []
    real = ops.paged_attention_bkgd

    def record(lens, layer):
        if int(layer) == 0:
            seen.append(np.asarray(lens))

    def spy(q, k, v, bt, lens, layer, **kw):
        jax.debug.callback(record, lens, layer)
        return real(q, k, v, bt, lens, layer, **kw)

    monkeypatch.setattr(ops, "paged_attention_bkgd", spy)
    T, P, B = 8, 12, 3
    eng = PagedServingEngine(smoke_model, n_slabs=14, blocks_per_slab=2,
                             page_T=T, max_batch=B, max_seq=P * T,
                             policy="mdc", compact_trigger=2,
                             compact_batch=3, seed=0, use_pallas=True,
                             max_decode_chunk=8, stop_token=335,
                             admit_every_dispatch=False)
    rids, news = _mixed_stream(eng, smoke_model.cfg.vocab_size)
    eng.run_to_completion()
    assert any(len(eng.finished[r]) < n for r, n in zip(rids, news))
    jax.effects_barrier()
    m = eng.metrics()
    assert len(seen) > eng.dispatches           # multi-token dispatches
    assert m["attn_pages_read"] == sum(
        int(np.ceil(s / T).sum()) for s in seen)
    assert m["attn_pages_table"] == len(seen) * B * P
    assert 0 < m["attn_pages_read"] < m["attn_pages_table"]
    assert any((s == 0).any() for s in seen)     # an idle slot reads nothing


def test_stop_token_chunked_equals_singlestep(smoke_model):
    """Mid-dispatch stops must be invisible to the tokens: a multi-token
    dispatch engine truncates exactly where the single-token engine does.
    (Pool counters may differ — data-dependent completion shifts admission
    events between dispatch boundaries — but tokens may not.)"""
    import jax

    params = smoke_model.init(jax.random.PRNGKey(0))

    def run(chunk, stop):
        eng = PagedServingEngine(smoke_model, n_slabs=14, blocks_per_slab=2,
                                 page_T=8, max_batch=3, max_seq=96,
                                 policy="mdc", params=params,
                                 compact_trigger=2, compact_batch=3,
                                 max_decode_chunk=chunk, stop_token=stop)
        rids, _ = _mixed_stream(eng, smoke_model.cfg.vocab_size, seed=1)
        eng.run_to_completion()
        eng.pool.check_invariants()
        return {r: eng.finished[r] for r in rids}

    # the stop token is read off a stop-free run — a token some stream
    # decodes before its last position — so the early exit this test needs
    # happens whatever the random weights are
    free = run(1, None)
    mid = collections.Counter(t for out in free.values() for t in out[1:-1])
    stop = mid.most_common(1)[0][0]
    results = [run(chunk, stop) for chunk in (1, 8)]
    assert results[0] == results[1]
    assert any(len(out) < len(free[r]) and out[-1] == stop
               for r, out in results[0].items()), \
        "stream must contain at least one early exit"


def test_stop_token_on_prefill_token_finishes_at_admission(smoke_model):
    """If the prefill's first emitted token is the stop token, the request
    completes during admission and step() must still report it."""
    from repro.models import transformer as tfm
    import jax

    prompt = np.arange(1, 6)
    params = smoke_model.init(jax.random.PRNGKey(0))
    first = tfm.greedy_decode(params, prompt, smoke_model.cfg, 1)[0]
    eng = PagedServingEngine(smoke_model, n_slabs=8, blocks_per_slab=2,
                             page_T=8, max_batch=2, max_seq=64, policy="mdc",
                             params=params, stop_token=first)
    rid = eng.submit(prompt, 10)
    done = eng.step()
    assert done == [rid]
    assert eng.finished[rid] == [first]
    assert not eng.has_work()
    eng.pool.check_invariants()


# ------------------------------------------- admission accounting (fixes)

def test_admission_reserve_is_in_slab_units(smoke_model):
    """Regression (ISSUE 5): ``compact_trigger`` is a *slab* count, so the
    admission reserve is ``compact_trigger * blocks_per_slab`` blocks — the
    old code added the raw trigger to a block count, understating the
    reserve by blocks_per_slab× and admitting into the cleaner's headroom.
    At the boundary, admission must neither OOM nor starve."""
    import jax

    params = smoke_model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(smoke_model, n_slabs=5, blocks_per_slab=4,
                             page_T=8, max_batch=2, max_seq=96,
                             policy="mdc", params=params, compact_trigger=2,
                             compact_batch=2, max_decode_chunk=2)
    assert eng.pool.admission_reserve() == 2 * 4  # slabs -> blocks
    ra = eng.submit(np.arange(1, 49), 16)   # needs 8 of the 20 blocks
    rb = eng.submit(np.arange(1, 9), 56)    # needs 8 more
    eng.step()
    # A admitted; B must wait: 20 - 6 held = 14 free < need 8 + reserve 8.
    # (The old block-unit reserve, 8 + 2 <= 14, would admit B here.)
    assert eng.rid[0] == ra and rb not in eng.rid
    assert len(eng.queue) == 1
    while eng.queue:           # B admitted only once A's death frees blocks
        assert rb not in eng.rid
        eng.step()
    eng.run_to_completion()
    assert len(eng.finished[ra]) == 16 and len(eng.finished[rb]) == 56
    eng.pool.check_invariants()
    # no starvation at the exact boundary: a request sized need + reserve
    # == pool admits as soon as the pool is idle (reserve waived when
    # nothing is active, so whole-pool requests can still run)
    rc = eng.submit(np.arange(1, 9), 88)    # needs 12 = 20 - reserve
    eng.run_to_completion()
    assert len(eng.finished[rc]) == 88
    eng.pool.check_invariants()


def test_admission_need_is_net_of_cached_prefix(smoke_model):
    """Regression (ISSUE 5): a request whose prefix is cached only
    allocates the tail, so admission must charge it the *net* page need —
    the gross-need gate rejected admissible requests under pressure (the
    cached pages are spliced, not allocated, and while referenced by an
    active sequence they are not evictable either)."""
    import jax
    import jax.numpy as jnp

    params = smoke_model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(smoke_model, n_slabs=6, blocks_per_slab=2,
                             page_T=8, max_batch=2, max_seq=96,
                             policy="mdc", params=params, compact_trigger=1,
                             compact_batch=2, max_decode_chunk=2,
                             prefix_cache=True, pool_dtype=jnp.float32)
    sysp = np.random.default_rng(42).integers(
        1, smoke_model.cfg.vocab_size, size=40)  # 5 full pages
    rd = eng.submit(np.concatenate([sysp, [3] * 8]), 8)   # donor seeds tree
    eng.run_to_completion()
    assert eng.prefix_cache.n_pages >= 5
    rh = eng.submit(np.concatenate([sysp, [5] * 8]), 16)  # holder: active ref
    eng.step()
    assert rh in eng.rid
    # follower: gross need 8 pages won't fit (holder + referenced prefix
    # leave ~5 free), net-of-prefix need is 3 — must be admitted NOW
    rf = eng.submit(np.concatenate([sysp, [7] * 8]), 16)
    eng.step()
    assert rf in eng.rid and rh in eng.rid, \
        "net-of-prefix admission must run the follower alongside the holder"
    eng.run_to_completion()
    assert len(eng.finished[rh]) == 16 and len(eng.finished[rf]) == 16
    eng.pool.check_invariants()
    eng.prefix_cache.check_invariants()


@pytest.mark.parametrize("policy", ["mdc", "greedy", "age"])
def test_engine_policies_all_correct(smoke_model, policy):
    """Every cleaning policy must preserve decode correctness (they differ
    only in Wamp, not in results)."""
    prompt = (np.arange(2, 16) * 3) % smoke_model.cfg.vocab_size
    params, want = _dense_reference_decode(smoke_model, prompt, 6)
    eng = PagedServingEngine(smoke_model, n_slabs=10, blocks_per_slab=2,
                             page_T=8, max_batch=2, max_seq=48,
                             policy=policy, params=params,
                             compact_trigger=2, compact_batch=2)
    rid = eng.submit(prompt, 6)
    eng.run_to_completion()
    assert eng.finished[rid] == want
