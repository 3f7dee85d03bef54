"""How ``correct`` is decided for a served model: once the window has
closed and the engine is freed, a sample of the requests that finished in
the window (drawn from the seed, with the longest and with requests whose
pages the cleaner moved) is run through the plain reference over its
prompt and served tokens.  The number compared is the widest gap by which
a served token's reference logit lies below the reference's best logit at
that position (greedy decoding serves the best token, so a sound run reads
only rounding).  The control reads, at the same positions, the gap of the
token that the float8 reference puts first.
"""

from __future__ import annotations

import numpy as np

from . import reference


def sample(finished: dict, by_rid: dict, moved: set, t0: float, t1: float,
           seed: int, min_tokens: int, max_requests: int,
           max_moved: int = 3) -> list[int]:
    """Request ids to compare: the longest request that finished in the
    window, up to ``max_moved`` whose pages were moved, then others drawn
    from the seed until ``min_tokens`` served tokens or ``max_requests``."""
    rng = np.random.default_rng([int(seed) % 2**63, 7])
    pool = [r for r, q in by_rid.items()
            if q.done is not None and t0 <= q.done <= t1 and r in finished]
    if not pool:
        return []
    pool.sort()
    longest = max(pool, key=lambda r: (by_rid[r].plen + by_rid[r].n_out, r))
    pick = [longest]
    mv = [r for r in pool if r in moved and r != longest]
    pick += [mv[i] for i in rng.permutation(len(mv))[:max_moved]]
    rest = [r for r in pool if r not in pick]
    for i in rng.permutation(len(rest)):
        if (sum(len(finished[r]) for r in pick) >= min_tokens
                or len(pick) >= max_requests):
            break
        pick.append(rest[i])
    return pick


def gaps(w, cfg: dict, prompt: np.ndarray, out: list[int],
         control: bool = False) -> dict:
    """Reference logits over prompt + served tokens; the served tokens'
    widest gap below the best logit, and with ``control`` the float8
    reference's token's widest gap at the same positions."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(out[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = reference.logits_at(w, cfg, seq, rows)
    best = ref.max(-1)
    got = ref[np.arange(len(out)), np.asarray(out)]
    res = {"gap": float((best - got).max())}
    if control:
        low = reference.logits_at(w, cfg, seq, rows, mode="fp8")
        tok = low.argmax(-1)
        res["control_gap"] = float((best - ref[np.arange(len(out)),
                                               tok]).max())
    return res


def compare(w, cfg: dict, reqs: list, control: bool = False) -> dict:
    """``reqs``: (prompt, served tokens) pairs."""
    g, c = 0.0, 0.0
    for prompt, out in reqs:
        r = gaps(w, cfg, prompt, out, control)
        g = max(g, r["gap"])
        c = max(c, r.get("control_gap", 0.0))
    res = {"max_logit_gap": g}
    if control:
        res["control_gap"] = c
    return res
