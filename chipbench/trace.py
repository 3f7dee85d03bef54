"""Reduction of a JAX profiler trace to device busy time, kernel time and
the longest idle gaps, attributed to what the benchmark's host loop was
doing (its ``bench.*`` annotations) at the time.

On a TPU the device plane's "XLA Ops" line holds one event per executed
HLO instruction, named ``%<instruction> = <shape> <opcode>(...)``, with a
loop's body ops nested inside the loop's own event; its "XLA Modules" line
holds one event per executed program.

* Busy time is the union of the op intervals (nested ops count once)
  inside the ``bench.window`` annotation, averaged over the chips that ran
  anything.
* A kernel (``kernels.json``) is found by a substring of its instruction
  name (``%paged_attention_bkgd.9`` holds ``paged_attention``).  Its time
  is the sum of its op events (``"time": "op"``), or of the program
  events that ran it (``"time": "module"``: the whole dispatch, for a
  kernel whose work includes the ops around it, as the page move's
  scatter).
* The op breakdown leaves out loop and call containers, whose time is
  their body's.
"""

from __future__ import annotations

import bisect
import glob
import os

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."
CONTAINERS = (" while(", " conditional(", " call(")


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def _union(iv):
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def short(name: str) -> str:
    """The instruction name of an op event (``%fusion.150``)."""
    return name.split(" = ", 1)[0]


def reduce_planes(planes, kernels: dict) -> dict | None:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events``: ``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them."""
    host, dev, mods = [], {}, {}
    for pl in planes:
        if pl.name.startswith("/device:TPU:"):
            for ln in pl.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in ln.events]
                if ln.name == OP_LINE:
                    dev[pl.name] = evs
                elif ln.name == MODULE_LINE:
                    mods[pl.name] = sorted(evs)
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    dev = {k: v for k, v in dev.items() if v}
    if not dev:
        return None
    win = [(a, b) for a, b, n in host if n == WINDOW]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(a for v in dev.values() for a, _, _ in v)
        w1 = max(b for v in dev.values() for _, b, _ in v)
    busy_total, ops = 0.0, {}
    kt = {k: 0.0 for k in kernels}
    kn = {k: 0 for k in kernels}
    first_busy = None
    for plane in sorted(dev):
        clipped = []
        hit_mods = {k: set() for k in kernels}
        m_ev = mods.get(plane, [])
        m_starts = [a for a, _, _ in m_ev]
        for a, b, name in dev[plane]:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            sn = short(name)
            if not any(c in name for c in CONTAINERS):
                ops[sn] = ops.get(sn, 0.0) + (b - a)
            for k, spec in kernels.items():
                if spec["match"] in sn:
                    kn[k] += 1
                    if spec["time"] == "op":
                        kt[k] += b - a
                    else:
                        i = bisect.bisect_right(m_starts, a) - 1
                        if i >= 0 and m_ev[i][1] >= b:
                            hit_mods[k].add(i)
        for k, idx in hit_mods.items():
            kt[k] += sum(min(m_ev[i][1], w1) - max(m_ev[i][0], w0)
                         for i in idx)
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy)
        if first_busy is None:
            first_busy = busy
    n_chips = len(dev)
    gaps, prev = [], w0
    for a, b in first_busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [(e - s, n) for s, e, n in host
                  if s <= mid <= e and n != WINDOW]
        named.append((min(inside)[1] if inside else "host.other",
                      (b - a) * 1e-9))
    named.sort(key=lambda x: -x[1])
    top_ops = sorted(ops.items(), key=lambda x: -x[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n_chips * 1e-9,
        "chips": n_chips,
        "kernel_s": {k: v / n_chips * 1e-9 for k, v in kt.items()},
        "kernel_events": kn,
        "device_ops": [[n, v / n_chips * 1e-9] for n, v in top_ops],
        "idle_gaps": [[n, s] for n, s in named[:10]],
    }


def reduce_trace(trace_dir: str, kernels: dict) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, kernels)
