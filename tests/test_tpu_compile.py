"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret-mode tests cannot see what the Mosaic lowering refuses (block
shapes off the (8, 128) tiling, VMEM overruns); this file compiles each
kernel with ``interpret=False`` at qwen3-1.7b's widths and the serving
pool's geometry for one chip of a described ``v5e:2x2`` topology.  Nothing
runs, so there is no chip to need.

The topology is described inside a module fixture — never at import, in a
``skipif`` or in a ``parametrize`` — so every test worker collects the same
tests and only the worker that runs this file loads the TPU compiler.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

CFG = get_config("qwen3-1.7b")
B, T, MAX_SEQ = 32, 16, 2048                  # decode slots, page_T, max_seq
N_PAGES = 128 * 16 + 1                        # pool pages + the trash page
H, KH, D = CFG.n_heads, CFG.n_kv_heads, CFG.hd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (layers, query heads, kv heads, head dim) of the benchmark's
# configurations: qwen3-1.7b (G = 2) and the 8-layer Qwen3-32B stage (G = 8)
PAGED_SHAPES = {"qwen3-1.7b": (CFG.n_layers, H, KH, D),
                "qwen3-32b-8l": (8, 64, 8, 128)}


@pytest.mark.parametrize("name", sorted(PAGED_SHAPES))
def test_paged_attention_compiles(one_chip, name):
    from repro.kernels.paged_attention import paged_attention_bkgd

    L, h, kh, d = PAGED_SHAPES[name]
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    pool = sds((L, N_PAGES, kh, T, d), jnp.bfloat16)
    _compile(lambda q, k, v, bt, sl, li: paged_attention_bkgd(
                 q, k, v, bt, sl, li, interpret=False),
             sds((B, kh, h // kh, d), jnp.bfloat16), pool, pool,
             sds((B, MAX_SEQ // T), jnp.int32), sds((B,), jnp.int32),
             sds((), jnp.int32))


def test_segment_compact_compiles(one_chip):
    from repro.kernels.segment_compact import segment_compact

    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    # the engine's move: every layer's copy of a 64-page plan, pages viewed
    # as (Kh·T·D) payload rows of the flattened (L·pages) pool
    _compile(lambda p, s: segment_compact(p, s, interpret=False),
             sds((CFG.n_layers * N_PAGES, KH * T * D), jnp.bfloat16),
             sds((CFG.n_layers * 64,), jnp.int32))


def test_mdc_priority_compiles(one_chip):
    from repro.kernels.mdc_priority import mdc_priority

    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    _compile(lambda live, up2, now: mdc_priority(live, up2, now, S=16,
                                                 interpret=False),
             sds((128,), jnp.float32), sds((128,), jnp.float32),
             sds((), jnp.float32))


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention_bhsd

    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False),
             sds((1, H, 1024, D), jnp.bfloat16),
             sds((1, KH, 1024, D), jnp.bfloat16),
             sds((1, KH, 1024, D), jnp.bfloat16))
