"""Operations and bytes that the served work needs, counted from what the
algorithm needs and not from how the program computes it.

* Paged decode attention, per layer and per sequence at attention length
  c: reads the live K and V, c x Kh x hd x 2 tensors x 2 bytes (bf16),
  plus q and writes the output, H x hd x 2 bytes each; 4 x H x hd x c
  operations (q.k and p.v).  Pages past a sequence's length, slots that
  are empty and padding are not needed work.
* Page move: each moved page is read and written once, K and V, in every
  layer: 2 x 2 x L x Kh x T x hd x 2 bytes.
* Model step: 2 operations per weight of every matrix a token passes
  through (all layers' projections and MLP; the head only where a logit is
  needed: once per prefill, once per decoded token) plus causal attention,
  4 x H x hd per (query, key) pair.
"""

from __future__ import annotations

BF16 = 2


def _d(c: dict) -> tuple:
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    hd = c.get("head_dim") or d // H
    return (d, H, c["num_key_value_heads"], hd, c["intermediate_size"],
            c["vocab_size"], c["num_hidden_layers"])


def paged_attention(c: dict, lens) -> tuple[float, float]:
    """(operations, bytes) of decode attention over sequences whose
    attention lengths are ``lens`` (one entry per sequence per token)."""
    d, H, K, hd, ff, V, L = _d(c)
    n, s = len(lens), float(sum(lens))
    flops = L * 4.0 * H * hd * s
    byts = L * (s * K * hd * 2 * BF16 + n * 2 * H * hd * BF16)
    return flops, byts


def page_move(c: dict, pages: int, page_T: int) -> tuple[float, float]:
    d, H, K, hd, ff, V, L = _d(c)
    return 0.0, 2.0 * 2 * L * K * page_T * hd * BF16 * pages


def layer_weights(c: dict) -> int:
    d, H, K, hd, ff, V, L = _d(c)
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff


def model_flops(c: dict, prefills, decode_lens) -> float:
    """Operations of prefilling prompts of lengths ``prefills`` and of
    decode iterations at attention lengths ``decode_lens``."""
    d, H, K, hd, ff, V, L = _d(c)
    w = 2.0 * L * layer_weights(c)
    head = 2.0 * d * V
    att = 4.0 * L * H * hd
    f = 0.0
    for p in prefills:
        f += p * w + att * p * (p + 1) / 2 + head
    s = float(sum(decode_lens))
    return f + len(decode_lens) * (w + head) + att * s


def roofline_share(flops: float, byts: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """Least time the chip could take over the measured time, in %, and
    which bound sets it."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = byts / peak["hbm_bytes_per_s"]
    return 100.0 * max(tf, tb) / seconds, ("compute" if tf > tb
                                           else "memory")
