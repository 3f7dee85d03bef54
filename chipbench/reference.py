"""Plain reference forward pass of a dense decoder (Llama / Qwen3 family),
written from the published description and the Hugging Face config keys.
It imports nothing of the program under test.

Every matrix product is float32-accurate: the weights are bfloat16 values
(exact in bfloat16), and a float32 activation is split into three bfloat16
parts (hi + mid + lo carry its 24 significant bits), each multiplied with
float32 accumulation.  That is what ``precision=HIGHEST`` does on the TPU's
matrix unit, minus the passes for the weight's (empty) low parts, and it
never holds a float32 copy of a weight matrix.  Attention runs in float32
at ``HIGHEST``.

``mode="fp8"`` is the control: every matrix product takes its operands
through float8 e4m3 (per-row scale on the activation, per-output-column
scale on the weight, both over the contracted axis), the step below the
bfloat16 the configuration serves in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _split3(x):
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _dot_exact(a, b):
    """(M, K) float32 @ (K, N) bfloat16, float32-accurate."""
    return sum(jnp.dot(p, b, preferred_element_type=jnp.float32)
               for p in _split3(a))


def _fp8(x, axis):
    """float8 e4m3 quantisation of x over ``axis``: (values as bfloat16 —
    exact, e4m3 has 3 mantissa bits — and the float32 scale)."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    s = jnp.maximum(s, 1e-30) / E4M3_MAX
    q = (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.bfloat16), s


def _fake8(x, axis):
    q, s = _fp8(x, axis)
    return q.astype(jnp.float32) * s


def mm(a, b, mode):
    """a (M, K) float32 @ b (K, N) bfloat16 weights."""
    if mode == "fp8":
        qb, sb = _fp8(b, 0)
        return _dot_exact(_fake8(a, 1), qb) * sb
    return _dot_exact(a, b)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate-half rotary embedding; x (S, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, mode):
    """Causal GQA; q (S, H, hd), k/v (S, K, hd), float32."""
    S, H, hd = q.shape
    K = k.shape[1]
    if mode == "fp8":
        q, k, v = _fake8(q, 2), _fake8(k, 2), _fake8(v, 0)
    qg = q.reshape(S, K, H // K, hd)
    s = jnp.einsum("qkgd,tkd->kgqt", qg, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    if mode == "fp8":
        p = _fake8(p, 3)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)
    return o.reshape(S, H * hd)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits(w, tokens, rows, *, cfg, mode):
    c = dict(cfg)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    qk_norm = c.get("model_type") == "qwen3"
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = jnp.take(w["embed"], tokens, 0).astype(jnp.float32)

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        d, H, hd = a["wq"].shape
        K = a["wk"].shape[1]
        h = rmsnorm(x, lp["ln1"], eps)
        q = mm(h, a["wq"].reshape(d, H * hd), mode).reshape(S, H, hd)
        k = mm(h, a["wk"].reshape(d, K * hd), mode).reshape(S, K, hd)
        v = mm(h, a["wv"].reshape(d, K * hd), mode).reshape(S, K, hd)
        if qk_norm:
            q = rmsnorm(q, a["q_norm"], eps)
            k = rmsnorm(k, a["k_norm"], eps)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        x = x + mm(attention(q, k, v, mode), a["wo"].reshape(H * hd, d), mode)
        h = rmsnorm(x, lp["ln2"], eps)
        g = mm(h, m["w_gate"], mode)
        u = mm(h, m["w_up"], mode)
        return x + mm(jax.nn.silu(g) * u, m["w_down"], mode), None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    x = rmsnorm(jnp.take(x, rows, 0), w["final_norm"], eps)
    head = w["embed"].T if "lm_head" not in w else w["lm_head"]
    return mm(x, head, mode)


def _bucket(n: int, grain: int) -> int:
    return -(-n // grain) * grain


def logits_at(w, cfg: dict, tokens: np.ndarray, rows: np.ndarray,
              mode: str = "f32", grain: int = 512) -> np.ndarray:
    """Logits (len(rows), V) of the sequence ``tokens`` at positions
    ``rows`` (each row predicts the token after it).  Lengths are padded to
    a multiple of ``grain`` (padding sits after every row read, so the
    causal mask hides it) to bound the number of compiled shapes."""
    S, R = len(tokens), len(rows)
    tok = np.zeros(_bucket(S, grain), np.int32)
    tok[:S] = tokens
    rw = np.zeros(_bucket(R, 64), np.int32)
    rw[:R] = rows
    keep = ("rms_norm_eps", "rope_theta", "model_type")
    items = tuple(sorted((k, cfg[k]) for k in keep if k in cfg))
    out = _logits(w, jnp.asarray(tok), jnp.asarray(rw), cfg=items, mode=mode)
    return np.asarray(out[:R])
