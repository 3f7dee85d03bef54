"""Device idle time put down to the program's own host spans.

    python chipbench/spans.py <trace dir>

reads a JAX profile (a ``--trace 1`` run kept with
``CHIPBENCH_KEEP_TRACE=<dir>``) and prints one JSON object.  The engine and
the pool open ``engine.*`` and ``pool.*`` spans (``repro.obs.span``) on the
profiler's clock, beside the benchmark's ``bench.*`` annotations; this
reduces them inside ``bench.window``:

* ``spans``: per span name, ``n`` (spans that overlap the window),
  ``total_s`` (their time in it), ``self_s`` (the time in which the span
  is the innermost open span, i.e. its duration less the union of its
  children on the same thread) and ``idle_s`` (device-idle time inside its
  self intervals).
* ``idle_by_span``: idle seconds per innermost span over the whole window,
  ``bench.*`` annotations included and ``host.other`` where no span is
  open.  Every idle nanosecond lands in exactly one entry, so the entries
  sum to ``window_s - busy_s``.
* ``idle_gaps``: the ten longest idle gaps, each named by the innermost
  span at its midpoint (as ``chipbench/trace.py`` names them from the
  ``bench.*`` annotations alone).
* ``step_host_ms``: over the window's dispatching ``engine.step`` spans
  (those holding an ``engine.dispatch``), the mean of the span's duration
  less the ``engine.host_sync`` spans inside it: host work of a step.
* ``clean_ms_per_compaction``: ``pool.compact`` time in the window over
  the cycles that cleaned at least one slab (``victims`` > 0); cycles that
  found no victim cost time too and count in the numerator.

and, to look further: ``idle_by_path`` (idle seconds per chain of open
spans, outermost first, joined by ``>``), ``gap_paths`` (the chain at the
midpoint of each of the ten longest gaps, with the gap's offset from the
innermost span's start and that span's length, in ms) and ``sync_idle``
(the idle time inside ``engine.host_sync`` spans before the first device
op in the span, between ops, and after the last one).

Device busy time is the union of the first chip's op intervals, as in
``chipbench/trace.py``.
"""

from __future__ import annotations

import bisect
import json
import sys

if __package__ in (None, ""):
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench.trace import (HOST_PREFIX, OP_LINE, WINDOW, _union,  # noqa: E402
                             find_xplane)

PROGRAM = ("engine.", "pool.")
OTHER = "host.other"


def _host_spans(planes):
    """(start, end, name, stats) of every ``bench.*``, ``engine.*`` and
    ``pool.*`` event on the host thread that holds ``bench.window``."""
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(getattr(e, "stats", ()) or ()))
                   for e in ln.events
                   if e.name.startswith(PROGRAM + (HOST_PREFIX,))]
            if any(n == WINDOW for _, _, n, _ in evs):
                return evs
    return []


def _busy(planes, w0, w1):
    """The first chip's busy intervals inside [w0, w1]."""
    for pl in sorted(planes, key=lambda p: p.name):
        if not pl.name.startswith("/device:TPU:"):
            continue
        for ln in pl.lines:
            if ln.name != OP_LINE:
                continue
            iv = [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
                  for e in ln.events]
            iv = [(a, b) for a, b in iv if b > a]
            if iv:
                return _union(iv)
    return None


def reduce_spans(planes) -> dict | None:
    """The reduction above, or None where the trace has no device ops."""
    planes = list(planes)
    host = _host_spans(planes)
    win = [(a, b) for a, b, n, _ in host if n == WINDOW]
    if not win:
        return None
    w0, w1 = win[0]
    busy = _busy(planes, w0, w1)
    if busy is None:
        return None
    gaps, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    # spans clipped to the window, outermost first at equal starts
    sp = sorted(((max(a, w0), min(b, w1), n, st)
                 for a, b, n, st in host
                 if n != WINDOW and b > w0 and a < w1),
                key=lambda s: (s[0], -s[1]))
    # sweep: between consecutive boundaries the innermost open span (top
    # of the stack: spans on one thread nest) owns the time, busy or idle
    stats = {}
    idle_by = {}
    idle_path = {}
    parent = [None] * len(sp)
    cuts = sorted({w0, w1} | {x for s in sp for x in s[:2]}
                  | {x for g in gaps for x in g})
    stack, i, g = [], 0, 0
    for x0, x1 in zip(cuts, cuts[1:]):
        while stack and sp[stack[-1]][1] <= x0:
            stack.pop()
        while i < len(sp) and sp[i][0] <= x0:
            while stack and sp[stack[-1]][1] <= sp[i][0]:
                stack.pop()
            parent[i] = stack[-1] if stack else None
            stack.append(i)
            i += 1
        while stack and sp[stack[-1]][1] <= x0:
            stack.pop()
        while g < len(gaps) and gaps[g][1] <= x0:
            g += 1
        idle = g < len(gaps) and gaps[g][0] <= x0
        name = sp[stack[-1]][2] if stack else OTHER
        row = stats.setdefault(name, [0, 0, 0.0, 0.0])
        row[2] += x1 - x0
        if idle:
            row[3] += x1 - x0
            idle_by[name] = idle_by.get(name, 0.0) + (x1 - x0)
            path = ">".join(sp[k][2] for k in stack) or OTHER
            idle_path[path] = idle_path.get(path, 0.0) + (x1 - x0)
    for a, b, n, _ in sp:
        row = stats.setdefault(n, [0, 0, 0.0, 0.0])
        row[0] += 1
        row[1] += b - a
    spans = {n: {"n": r[0], "total_s": r[1] * 1e-9, "self_s": r[2] * 1e-9,
                 "idle_s": r[3] * 1e-9}
             for n, r in sorted(stats.items())
             if n.startswith(PROGRAM)}
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = sorted((s - e, s, n) for s, e, n, _ in sp if s <= mid <= e)
        named.append((inside[-1][2] if inside else OTHER, (b - a) * 1e-9,
                      ">".join(n for _, _, n in inside) or OTHER,
                      (a - inside[-1][1]) * 1e-6 if inside else None,
                      -inside[-1][0] * 1e-6 if inside else None))
    named.sort(key=lambda x: -x[1])
    # idle inside the host's waits: before the device starts, between its
    # ops, after it ends
    starts = [a for a, _ in busy]
    lead = mid_ = tail = 0
    for a, b, n, _ in sp:
        if n != "engine.host_sync":
            continue
        lo = max(bisect.bisect_right(starts, a) - 1, 0)
        hi = bisect.bisect_left(starts, b)
        iv = [(max(x, a), min(y, b)) for x, y in busy[lo:hi]]
        iv = [(x, y) for x, y in iv if y > x]
        if not iv:
            lead += b - a
            continue
        lead += iv[0][0] - a
        tail += b - iv[-1][1]
        mid_ += (iv[-1][1] - iv[0][0]) - sum(y - x for x, y in iv)
    # host work of a dispatching step: its time less the host syncs in it
    kids = {}
    for j, p in enumerate(parent):
        if p is not None:
            kids.setdefault(p, []).append(j)

    def within(j, name):
        out = []
        for c in kids.get(j, []):
            out += [c] if sp[c][2] == name else within(c, name)
        return out

    host_s = []
    for j, (a, b, n, _) in enumerate(sp):
        if n == "engine.step" and within(j, "engine.dispatch"):
            sync = sum(sp[c][1] - sp[c][0]
                       for c in within(j, "engine.host_sync"))
            host_s.append((b - a - sync) * 1e-9)
    cycles = [s for s in sp if s[2] == "pool.compact"]
    cleaned = sum(s[3].get("victims", 0) > 0 for s in cycles)
    busy_s = sum(b - a for a, b in busy) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_s,
        "idle_s": (w1 - w0) * 1e-9 - busy_s,
        "spans": spans,
        "idle_by_span": {n: v * 1e-9 for n, v in sorted(
            idle_by.items(), key=lambda x: -x[1])},
        "idle_gaps": [[n, s] for n, s, _, _, _ in named[:10]],
        "step_host_ms": (1e3 * sum(host_s) / len(host_s)
                         if host_s else None),
        "clean_ms_per_compaction": (
            1e3 * sum(b - a for a, b, _, _ in cycles) * 1e-9 / cleaned
            if cleaned else None),
        "idle_by_path": {n: v * 1e-9 for n, v in sorted(
            idle_path.items(), key=lambda x: -x[1])},
        "gap_paths": [[p, s, at, span] for _, s, p, at, span in named[:10]],
        "sync_idle": {"lead_s": lead * 1e-9, "mid_s": mid_ * 1e-9,
                      "tail_s": tail * 1e-9},
    }


def reduce_dir(trace_dir: str) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce_spans(ProfileData.from_file(path).planes)


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1])))
