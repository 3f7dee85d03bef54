"""The trace reduction on a small trace recorded on the chip (a v5e, the
``fixture.sat`` cell of ``data/BENCHMARK.json``: two layers, 0.3 s
window), checked against a brute-force reading of the same events.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_trace.py
"""

import json
import pathlib
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
KERNELS = json.loads((DATA.parents[1] / "kernels.json").read_text())


@pytest.fixture(scope="module")
def planes():
    path = trace.find_xplane(str(DATA / "trace"))
    assert path, "the recorded chip trace is missing"
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(path).planes)


def test_busy_kernels_and_gaps_match_a_brute_force_reading(planes):
    got = trace.reduce_planes(planes, KERNELS)
    assert got["chips"] == 1
    host = [e for pl in planes if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events if e.name == "bench.window"]
    w0 = host[0].start_ns
    w1 = w0 + host[0].duration_ns
    assert got["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    ops = [e for pl in planes if pl.name.startswith("/device:TPU:")
           for ln in pl.lines if ln.name == trace.OP_LINE for e in ln.events]
    # busy: a 10 ns timeline of the window, marked where any op runs
    grid = np.zeros(int((w1 - w0) // 10) + 2, bool)
    for e in ops:
        a = max(e.start_ns, w0)
        b = min(e.start_ns + e.duration_ns, w1)
        if b > a:
            grid[int((a - w0) // 10):int(np.ceil((b - w0) / 10))] = True
    assert got["busy_s"] == pytest.approx(grid.sum() * 1e-8, rel=0.01)
    assert 0 < got["busy_s"] < got["window_s"]
    # kernel time: the matching events' durations, summed
    def inside(e):
        return e.start_ns < w1 and e.start_ns + e.duration_ns > w0

    pa = [e for e in ops if e.name.startswith("%paged_attention_bkgd")
          and inside(e)]
    assert pa and got["kernel_events"]["paged_attention"] == len(pa)
    assert got["kernel_s"]["paged_attention"] == pytest.approx(
        sum(min(e.start_ns + e.duration_ns, w1) - max(e.start_ns, w0)
            for e in pa) * 1e-9)
    # the page move's time is that of the programs that ran its kernel
    mods = [e for pl in planes if pl.name.startswith("/device:TPU:")
            for ln in pl.lines if ln.name == trace.MODULE_LINE
            for e in ln.events if inside(e)]
    mv = [e for e in ops if e.name.startswith("%segment_compact")
          and inside(e)]
    assert mv
    hit = {id(m): m for e in mv for m in mods
           if m.start_ns <= e.start_ns
           and e.start_ns + e.duration_ns <= m.start_ns + m.duration_ns}
    assert got["kernel_s"]["page_move"] == pytest.approx(
        sum(m.duration_ns for m in hit.values()) * 1e-9)
    assert got["kernel_s"]["page_move"] > sum(e.duration_ns
                                              for e in mv) * 1e-9
    # the gaps are the window less the busy time, longest first
    gaps = [s for _, s in got["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= got["window_s"] - got["busy_s"] + 1e-9
    assert all(n.startswith("bench.") or n == "host.other"
               for n, _ in got["idle_gaps"])


def test_union_merges_overlaps_once():
    assert trace._union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_no_device_plane_reads_nothing():
    pl = NS(name="/host:CPU", lines=[NS(name="python", events=[])])
    assert trace.reduce_planes([pl], KERNELS) is None
