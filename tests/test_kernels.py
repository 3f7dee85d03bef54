"""Per-kernel sweeps: Pallas (interpret mode) vs pure-jnp ref oracles.

Shapes sweep ragged/aligned lengths, GQA group sizes and dtypes; tolerances
are dtype-dependent (bf16 inputs accumulate in f32 in both kernel and ref).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policies
from repro.kernels import ops, ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,Kh,D", [
    (1, 128, 128, 4, 4, 64),     # MHA, aligned
    (2, 256, 256, 8, 2, 64),     # GQA 4:1
    (1, 200, 200, 4, 1, 32),     # MQA, ragged seq (pad+mask path)
    (1, 64, 192, 2, 2, 128),     # cross-shape kv (prefill continuation)
    (2, 96, 96, 6, 3, 16),       # odd groups, tiny head dim
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, Sq, Skv, H, Kh, D, causal, dtype):
    if causal and Sq != Skv:
        pytest.skip("causal requires square q/kv here")
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = rand(ks[0], (B, Sq, H, D), dtype)
    k = rand(ks[1], (B, Skv, Kh, D), dtype)
    v = rand(ks[2], (B, Skv, Kh, D), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_attention_block_size_invariance():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (1, 160, 4, 64), jnp.float32)
    k = rand(ks[1], (1, 160, 2, 64), jnp.float32)
    v = rand(ks[2], (1, 160, 2, 64), jnp.float32)
    outs = [ops.flash_attention(q, k, v, causal=True, q_block=qb, kv_block=kb)
            for qb, kb in [(32, 32), (64, 128), (128, 64), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_chunked_path():
    """The kernel must agree with the XLA chunked path the models lower."""
    from repro.models.attention import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = rand(ks[0], (2, 128, 8, 32), jnp.float32)
    k = rand(ks[1], (2, 128, 4, 32), jnp.float32)
    v = rand(ks[2], (2, 128, 4, 32), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True)
    want = chunked_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ paged attention

def _paged_case(B, H, Kh, D, T, P, L, dtype, seed=3):
    """q, stacked (L, pages, Kh, T, D) pools and a block table of disjoint
    random pages per sequence (as the slab allocator would give)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n_pages = B * P + 5
    q = rand(ks[0], (B, H, D), dtype)
    k_pools = rand(ks[1], (L, n_pages, Kh, T, D), dtype)
    v_pools = rand(ks[2], (L, n_pages, Kh, T, D), dtype)
    perm = jax.random.permutation(ks[3], n_pages)[: B * P]
    return q, k_pools, v_pools, perm.reshape(B, P).astype(jnp.int32)


# The kernel walks blocks of pages_per_block(T, P) pages (512 tokens): at
# T 32 a block is 16 pages, at T 16 it is 32.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Kh,D,T,P,lens,L", [
    pytest.param(2, 4, 4, 64, 16, 4, None, 1, id="2-4-4-64-16-4"),
    # GQA 4:1
    pytest.param(3, 8, 2, 32, 8, 6, None, 1, id="3-8-2-32-8-6"),
    # MQA
    pytest.param(1, 4, 1, 128, 32, 3, None, 1, id="1-4-1-128-32-3"),
    # one token; mid-page; on a page boundary; on a block boundary
    pytest.param(4, 4, 2, 64, 32, 20, (1, 45, 64, 512), 1, id="edges"),
    # a full table whose width (20) is no multiple of the block (16 pages)
    pytest.param(2, 4, 2, 64, 32, 20, (640, 600), 1, id="full-table"),
    # a stacked pool of 3 layers, read at layer 2
    pytest.param(3, 8, 2, 32, 16, 6, None, 3, id="layer-2-of-3"),
    # GQA 8:1 over two blocks
    pytest.param(2, 16, 2, 128, 16, 40, (600, 17), 1, id="gqa-8"),
])
def test_paged_attention_matches_ref(B, H, Kh, D, T, P, lens, L, dtype):
    q, k_pools, v_pools, block_tables = _paged_case(B, H, Kh, D, T, P, L,
                                                    dtype)
    if lens is None:     # ragged lengths incl. exactly-one-page and full
        lens = np.linspace(1, P * T, B).astype(np.int32)
    seq_lens = jnp.asarray(lens, jnp.int32)
    got = ops.paged_attention(q, k_pools, v_pools, block_tables, seq_lens,
                              layer=L - 1)
    want = ref.paged_attention_ref(q, k_pools[L - 1], v_pools[L - 1],
                                   block_tables, seq_lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("interpret", ["interpret", "interpret_params"])
def test_paged_attention_never_reads_dead_pages(interpret):
    """Pages past each sequence's live length hold NaN in the pool, and so
    do the rows past the length in its last page; the table's dead entries
    name pages past the pool's end.  The kernel must neither fetch nor mix
    them: finite output, equal to the reference over the same pools with
    the NaNs zeroed.  Under ``InterpretParams`` unfetched VMEM is NaN too,
    and a read past the block table or the pool raises."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.paged_attention import paged_attention_bkgd
    B, H, Kh, D, T, P, L = 6, 8, 2, 64, 32, 20, 2
    q, k_pools, v_pools, bt = _paged_case(B, H, Kh, D, T, P, L, jnp.float32)
    # empty sequences first, between and last: nothing to fetch or prefetch
    lens = np.array([0, 1, 0, 45, 600, 0], np.int32)
    k, v, bt_np = np.array(k_pools), np.array(v_pools), np.asarray(bt)
    for b, n in enumerate(lens):
        last = (n - 1) // T
        for pool in (k, v):
            pool[:, bt_np[b, last + 1:]] = np.nan
            pool[:, bt_np[b, last], :, n - last * T:] = np.nan
    dead = np.arange(P)[None] > (lens[:, None] - 1) // T
    bt_dead = np.where(dead, k.shape[1] + np.arange(P)[None], bt_np)
    mode = True if interpret == "interpret" else pltpu.InterpretParams()
    got = paged_attention_bkgd(q.reshape(B, Kh, H // Kh, D), jnp.asarray(k),
                               jnp.asarray(v), jnp.asarray(bt_dead, jnp.int32),
                               jnp.asarray(lens), jnp.int32(1),
                               interpret=mode)
    got = np.asarray(got).reshape(B, H, D)
    assert np.isfinite(got).all()
    live = lens > 0
    assert (got[~live] == 0).all()                 # nothing attended
    want = ref.paged_attention_ref(q, jnp.asarray(np.nan_to_num(k[1])),
                                   jnp.asarray(np.nan_to_num(v[1])), bt,
                                   jnp.asarray(lens))
    np.testing.assert_allclose(got[live], np.asarray(want)[live],
                               **TOL[jnp.float32])


def test_paged_attention_equals_dense_decode():
    """Paged read through a shuffled pool == dense contiguous attention."""
    B, H, Kh, D, T, P = 2, 4, 2, 32, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = rand(ks[0], (B, H, D), jnp.float32)
    kv_len = 26  # inside page 3
    k_seq = rand(ks[1], (B, P * T, Kh, D), jnp.float32)
    v_seq = rand(ks[2], (B, P * T, Kh, D), jnp.float32)
    # scatter the dense cache into a pool at random page slots
    n_pages = B * P
    perm = np.asarray(jax.random.permutation(ks[3], n_pages))
    k_pool = np.zeros((n_pages, Kh, T, D), np.float32)
    v_pool = np.zeros((n_pages, Kh, T, D), np.float32)
    bt = np.zeros((B, P), np.int32)
    for b in range(B):
        for p in range(P):
            phys = perm[b * P + p]
            bt[b, p] = phys
            # pool pages are (Kh, T, D): head-major within the page
            k_pool[phys] = np.asarray(k_seq[b, p * T:(p + 1) * T]).swapaxes(
                0, 1)
            v_pool[phys] = np.asarray(v_seq[b, p * T:(p + 1) * T]).swapaxes(
                0, 1)
    seq_lens = jnp.full((B,), kv_len, jnp.int32)
    got = ops.paged_attention(q, jnp.asarray(k_pool)[None],
                              jnp.asarray(v_pool)[None], jnp.asarray(bt),
                              seq_lens, layer=0)
    from repro.models.attention import decode_attention
    want = decode_attention(q[:, None], k_seq, v_seq, seq_lens)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------- segment compact

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("N,E,M", [(32, 256, 16), (7, 100, 7), (64, 8192, 64),
                                   (16, 130, 5)])
def test_segment_compact_matches_ref(N, E, M, dtype):
    key = jax.random.PRNGKey(5)
    if dtype == jnp.int32:
        pool = jax.random.randint(key, (N, E), 0, 1000, jnp.int32)
    else:
        pool = rand(key, (N, E), dtype)
    src = jax.random.randint(jax.random.PRNGKey(6), (M,), 0, N, jnp.int32)
    got = ops.segment_compact(pool, src, tile=1024)
    want = ref.segment_compact_ref(pool, src)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_compact_multi_window(dtype):
    """A block payload larger than one window is copied in several
    (rows, 128) windows: 8192 elements at tile=4096 is two 32-row windows."""
    pool = rand(jax.random.PRNGKey(8), (24, 8192), dtype)
    src = jax.random.randint(jax.random.PRNGKey(9), (10,), 0, 24, jnp.int32)
    got = ops.segment_compact(pool, src, tile=4096)
    want = ref.segment_compact_ref(pool, src)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------------------------- mdc priority

@pytest.mark.parametrize("N,S", [(100, 512), (1024, 512), (4097, 64), (3, 32)])
def test_mdc_priority_matches_numpy_policy(N, S):
    rng = np.random.default_rng(N)
    live = rng.integers(0, S + 1, N)
    up2 = rng.uniform(0, 1e6, N)
    u_now = 1.5e6
    got = np.asarray(ops.mdc_priority(jnp.asarray(live), jnp.asarray(up2),
                                      u_now, S=S))
    want = policies.key_mdc(live=live, S=S, up2=up2, u_now=u_now)
    finite = np.isfinite(want)
    np.testing.assert_allclose(got[finite], want[finite].astype(np.float32),
                               rtol=1e-5)
    assert (np.isinf(got) == ~finite).all()


def test_mdc_priority_matches_jnp_ref():
    rng = np.random.default_rng(0)
    live = jnp.asarray(rng.integers(0, 129, 777))
    up2 = jnp.asarray(rng.uniform(0, 100.0, 777))
    got = ops.mdc_priority(live, up2, 200.0, S=128)
    want = ref.mdc_priority_ref(live, up2, 200.0, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_mdc_select_victims_orders_like_simulator():
    """On-device victim selection == the numpy simulator's selection."""
    rng = np.random.default_rng(1)
    N, S, k = 256, 128, 8
    live = rng.integers(1, S, N)   # no empty/full edge cases: strict order
    up2 = rng.uniform(0, 1e5, N)
    u_now = 2e5
    ids, valid = ops.mdc_select_victims(jnp.asarray(live), jnp.asarray(up2),
                                        u_now, S=S, k=k)
    want = policies.select_victims(
        "mdc", k, live=live, S=S, up2=up2,
        seal_time=np.zeros(N), u_now=u_now, seg_prob=np.zeros(N),
        eligible=np.ones(N, bool))
    assert np.asarray(valid).all()
    np.testing.assert_array_equal(np.sort(np.asarray(ids)), np.sort(want))
