"""The reduction of the program's host spans (chipbench/spans.py), on
synthetic planes against values computed by hand, and on the recorded v5e
trace (which holds no program spans) against chipbench/trace.py.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_spans.py
"""

import json
import pathlib
from types import SimpleNamespace as NS

import pytest

from chipbench import spans, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def ev(name, a, b, **stats):
    return NS(name=name, start_ns=int(a * MS), duration_ns=int((b - a) * MS),
              stats=list(stats.items()))


def planes():
    """A 100 ms window; the chip busy over [10, 30] and [50, 90] (an op
    nested in the first), so idle over [0, 10], [30, 50] and [90, 100]."""
    main = [
        ev("bench.window", 0, 100),
        ev("bench.step", 4, 94.5),
        ev("engine.step", 6, 94, dispatch=0),
        ev("engine.admit", 6, 20),
        ev("engine.alloc", 7, 12),
        ev("pool.compact", 8, 11, victims=2, moves=5),
        ev("engine.compaction", 9, 10, moves=5),
        ev("engine.prefill", 12, 14),
        ev("engine.host_sync", 14, 19),
        ev("engine.dispatch", 20, 22),
        ev("engine.host_sync", 22, 88),
        ev("engine.emit", 88, 94),
        # a step that admitted nothing and dispatched nothing
        ev("bench.step", 95.5, 99),
        ev("engine.step", 96, 98.5, dispatch=1),
        ev("engine.admit", 96, 98),
        ev("pool.compact", 96.5, 97.5, victims=0, moves=0),
    ]
    other = [ev("engine.step", 0, 100)]   # another thread: not read
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=main),
                                       NS(name="worker", events=other)])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name=trace.OP_LINE, events=[ev("%a", 10, 30), ev("%b", 12, 14),
                                       ev("%c", 50, 90)]),
        NS(name=trace.MODULE_LINE, events=[])])
    return [host, dev]


def test_idle_is_split_across_innermost_spans():
    r = spans.reduce_spans(planes())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.06)
    want = {"engine.host_sync": 20, "host.other": 6, "engine.emit": 4,
            "bench.step": 3.5, "engine.admit": 2, "pool.compact": 2,
            "engine.alloc": 1, "engine.compaction": 1, "engine.step": 0.5}
    assert r["idle_by_span"] == pytest.approx(
        {k: v * 1e-3 for k, v in want.items()})
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert list(r["idle_by_span"])[0] == "engine.host_sync"


def test_span_counts_total_self_and_idle():
    r = spans.reduce_spans(planes())
    # name: (n, total, self, idle) in ms
    want = {"engine.step": (2, 90.5, 0.5, 0.5),
            "engine.admit": (2, 16, 3, 2),
            "engine.alloc": (1, 5, 2, 1),
            "pool.compact": (2, 4, 3, 2),
            "engine.compaction": (1, 1, 1, 1),
            "engine.prefill": (1, 2, 2, 0),
            "engine.host_sync": (2, 71, 71, 20),
            "engine.dispatch": (1, 2, 2, 0),
            "engine.emit": (1, 6, 6, 4)}
    assert set(r["spans"]) == set(want)
    for name, (n, tot, own, idle) in want.items():
        got = r["spans"][name]
        assert got["n"] == n, name
        assert got["total_s"] == pytest.approx(tot * 1e-3), name
        assert got["self_s"] == pytest.approx(own * 1e-3), name
        assert got["idle_s"] == pytest.approx(idle * 1e-3), name


def test_gaps_are_named_by_the_innermost_span_at_their_midpoint():
    r = spans.reduce_spans(planes())
    assert r["idle_gaps"] == [["engine.host_sync", pytest.approx(0.02)],
                              ["bench.step", pytest.approx(0.01)],
                              ["host.other", pytest.approx(0.01)]]


def test_step_host_time_and_cleaning_cycle_time():
    r = spans.reduce_spans(planes())
    # the one dispatching step: 88 ms less its two host syncs (5 + 66)
    assert r["step_host_ms"] == pytest.approx(17.0)
    # 4 ms of cleaning over the one cycle that cleaned a slab
    assert r["clean_ms_per_compaction"] == pytest.approx(4.0)


def test_idle_by_chain_of_spans_and_inside_the_host_syncs():
    r = spans.reduce_spans(planes())
    path = r["idle_by_path"]
    assert path["bench.step>engine.step>engine.host_sync"] == \
        pytest.approx(0.02)
    assert path["host.other"] == pytest.approx(0.006)
    assert path["bench.step>engine.step>engine.admit>engine.alloc>"
                "pool.compact>engine.compaction"] == pytest.approx(0.001)
    assert sum(path.values()) == pytest.approx(r["idle_s"])
    # the longest gap opens 8 ms into the 66 ms host sync of the dispatch
    assert r["gap_paths"][0] == [
        "bench.step>engine.step>engine.host_sync", pytest.approx(0.02),
        pytest.approx(8.0), pytest.approx(66.0)]
    # both host syncs start and end on busy time; 20 ms idle between ops
    assert r["sync_idle"] == pytest.approx(
        {"lead_s": 0.0, "mid_s": 0.02, "tail_s": 0.0})


def test_without_program_spans_nothing_is_read():
    pl = planes()
    pl[0].lines[0].events = [e for e in pl[0].lines[0].events
                             if e.name.startswith("bench.")]
    r = spans.reduce_spans(pl)
    assert r["spans"] == {}
    assert r["step_host_ms"] is None and r["clean_ms_per_compaction"] is None
    assert set(r["idle_by_span"]) == {"bench.step", "host.other"}
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])


def test_recorded_trace_reads_as_the_trace_reduction():
    """The chip trace holds only ``bench.*`` annotations: the gaps and busy
    time are those of chipbench/trace.py, and the idle time splits over the
    annotations without loss."""
    from jax.profiler import ProfileData
    path = trace.find_xplane(str(DATA / "trace"))
    pl = list(ProfileData.from_file(path).planes)
    kernels = json.loads((DATA.parents[1] / "kernels.json").read_text())
    old = trace.reduce_planes(pl, kernels)
    r = spans.reduce_spans(pl)
    assert r["window_s"] == pytest.approx(old["window_s"])
    assert r["busy_s"] == pytest.approx(old["busy_s"])
    assert r["idle_gaps"] == old["idle_gaps"]
    assert r["spans"] == {}
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"], rel=1e-9)
