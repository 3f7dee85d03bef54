"""Seeded weights for a dense decoder, made on the device in one jitted call
in the type they are served in (bfloat16 matrices, float32 norm scales).

The tree has the serving engine's layout (stacked layers; ``wq`` is
(L, d, H, hd), ``wo`` (L, H, hd, d), ...).  The values come from the
benchmark alone: the engine and the reference are both handed this tree.
Per-layer leaves are drawn under ``lax.map`` so no float32 copy of a whole
stacked matrix is ever live (at Qwen3-32B widths one such copy is 4.2 GB).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    return dict(d=d, H=H, K=c["num_key_value_heads"],
                hd=c.get("head_dim") or d // H, ff=c["intermediate_size"],
                V=c["vocab_size"], L=c["num_hidden_layers"],
                tied=bool(c.get("tie_word_embeddings", False)),
                # Qwen3 normalises each head's q and k (RMSNorm over hd)
                qk_norm=c.get("model_type") == "qwen3")


def layer_shapes(c: dict) -> dict:
    """(shape, std) of one layer's matrices; std 0 marks a norm scale."""
    m = dims(c)
    d, H, K, hd, ff = m["d"], m["H"], m["K"], m["hd"], m["ff"]
    s = {
        ("attn", "wq"): ((d, H, hd), 1 / math.sqrt(d)),
        ("attn", "wk"): ((d, K, hd), 1 / math.sqrt(d)),
        ("attn", "wv"): ((d, K, hd), 1 / math.sqrt(d)),
        ("attn", "wo"): ((H, hd, d), 1 / math.sqrt(H * hd)),
        ("mlp", "w_gate"): ((d, ff), 1 / math.sqrt(d)),
        ("mlp", "w_up"): ((d, ff), 1 / math.sqrt(d)),
        ("mlp", "w_down"): ((ff, d), 1 / math.sqrt(ff)),
        ("ln1",): ((d,), 0.0),
        ("ln2",): ((d,), 0.0),
    }
    if m["qk_norm"]:
        s[("attn", "q_norm")] = ((hd,), 0.0)
        s[("attn", "k_norm")] = ((hd,), 0.0)
    return s


def _leaf(key, shape, std):
    if std == 0.0:  # a norm scale: 1 + N(0, 0.1^2), kept in float32
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=0)
def _make(cfg_items: tuple, key):
    c = dict(cfg_items)
    m = dims(c)
    shapes = layer_shapes(c)
    k_emb, k_head, k_fin, k_layers = jax.random.split(key, 4)
    out = {"embed": _leaf(k_emb, (m["V"], m["d"]), 0.02),
           "final_norm": _leaf(k_fin, (m["d"],), 0.0),
           "blocks": {"attn": {}, "mlp": {}}}
    if not m["tied"]:
        out["lm_head"] = _leaf(k_head, (m["d"], m["V"]), 1 / math.sqrt(m["d"]))
    names = sorted(shapes)
    for i, name in enumerate(names):
        shape, std = shapes[name]
        keys = jax.random.split(jax.random.fold_in(k_layers, i), m["L"])
        leaf = jax.lax.map(lambda k: _leaf(k, shape, std), keys)
        node = out["blocks"]
        for part in name[:-1]:
            node = node[part]
        node[name[-1]] = leaf
    return out


def key_from_seed(seed: int, salt: int = 0):
    """A JAX key from any whole-number seed (runs pass seeds above 2**32)."""
    ss = np.random.SeedSequence([int(seed) % 2**64, int(seed) >> 64, salt])
    return jax.random.PRNGKey(int(ss.generate_state(1, np.uint32)[0]))


def make_weights(c: dict, seed: int):
    """The weight tree for config ``c`` (HF-style keys) from ``seed``."""
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size",
            "num_hidden_layers", "tie_word_embeddings", "model_type")
    items = tuple(sorted((k, c[k]) for k in keep if k in c))
    return jax.block_until_ready(_make(items, key_from_seed(seed, 1)))
