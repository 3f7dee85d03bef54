"""Observability subsystem (repro.obs, DESIGN.md §12).

Pins the contracts the rest of the repo leans on: the tracer's bounded ring
(never exceeds capacity, drops oldest first), the Chrome-trace export schema
(valid events, B/E spans nest per (pid, tid), stable integer pid/tid), the
engine's golden ``metrics()`` schema and its single pluggable clock, the
``StoreStats.wamp()`` zero-write fix with ``per_stream_wamp``, the
MetricsLogger delta semantics, death-prediction calibration end to end
(core kill path → per-stream misroute rate + lifetime histograms), and the
engine's and pool's spans on the JAX profiler's clock (names, nesting, and
counts against the engine's counters, read from a CPU profile).
"""

import io
import json

import numpy as np
import pytest
from _hyp import given, settings, st  # degrades to skips without hypothesis

from repro.core.logstructure import FrameLog, Placement, StoreStats
from repro.obs import DeathCalibration, MetricsLogger, Tracer


class Tick:
    """Deterministic monotonic clock: each call advances by ``dt``."""

    def __init__(self, t0: float = 1000.0, dt: float = 0.001):
        self.t = t0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


def _check_chrome_trace(doc: dict) -> None:
    """Schema check: the invariants Perfetto/chrome://tracing rely on."""
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    stacks: dict[tuple, list] = {}
    for ev in doc["traceEvents"]:
        assert ev["ph"] in "BEiCbne", ev
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] in "bne":   # async events need an id to form a track
            assert "id" in ev, ev
        lane = (ev["pid"], ev["tid"])
        if ev["ph"] == "B":
            stacks.setdefault(lane, []).append(ev["name"])
        elif ev["ph"] == "E":
            assert stacks.get(lane), f"E without open B on {lane}: {ev}"
            assert stacks[lane].pop() == ev["name"], \
                f"span close out of order on {lane}: {ev}"
    assert all(not s for s in stacks.values()), f"unclosed spans: {stacks}"
    json.dumps(doc)   # exported document must round-trip as plain JSON


# ------------------------------------------------------------------ tracer

def test_tracer_span_nesting_and_export(tmp_path):
    tr = Tracer(capacity=64, clock=Tick())
    with tr.span("step", cat="engine"):
        with tr.span("admit"):
            tr.instant("queued", reqs=3)
        with tr.span("dispatch"):
            pass
    tr.counter("pool", free_blocks=7, queue_depth=2)
    tr.async_begin("req", 0, tid=1, cat="request", prompt_len=11)
    tr.async_instant("req.admit", 0, tid=1, cat="request")
    tr.async_end("req", 0, tid=1, cat="request", tokens=4)
    path = tmp_path / "t.json"
    doc = tr.export(path)
    _check_chrome_trace(doc)
    _check_chrome_trace(json.loads(path.read_text()))
    names = [e["name"] for e in doc["traceEvents"]]
    assert names[0] == "step" and "req.admit" in names
    # ts is µs relative to construction, monotone under a monotone clock
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)
    assert doc["otherData"]["dropped_events"] == 0


def test_tracer_args_coerce_numpy():
    tr = Tracer(capacity=8, clock=Tick())
    tr.instant("x", e=np.float64(0.5), n=np.int64(3),
               arr=np.arange(2), s="ok")
    args = tr.events()[0]["args"]
    assert args == {"e": 0.5, "n": 3, "arr": [0, 1], "s": "ok"}
    json.dumps(tr.export())


def test_tracer_ring_drops_oldest_first():
    tr = Tracer(capacity=4, clock=Tick())
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4 and tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]


@given(cap=st.integers(min_value=1, max_value=50),
       n=st.integers(min_value=0, max_value=200))
@settings(max_examples=60, deadline=None)
def test_tracer_ring_bounded_property(cap, n):
    """The ring never exceeds capacity and keeps exactly the newest events
    in order; ``dropped`` accounts for every evicted one."""
    tr = Tracer(capacity=cap, clock=Tick())
    for i in range(n):
        tr.instant(f"e{i}")
    assert len(tr) == min(cap, n)
    assert tr.dropped == max(0, n - cap)
    assert [e["name"] for e in tr.events()] \
        == [f"e{i}" for i in range(max(0, n - cap), n)]


# --------------------------------------------- store hooks (segment events)

def test_framelog_emits_segment_lifecycle_events():
    tr = Tracer(capacity=256, clock=Tick())
    log = FrameLog(4, 2)
    log.tracer = tr
    log.place(np.arange(6), Placement(up2=np.arange(6, dtype=np.float64)))
    log.kill_slots(np.array([1, 1]), np.array([0, 1]))   # thin out a victim
    log.evacuate(np.array([1]))
    names = [e["name"] for e in tr.events()]
    assert "seg.open" in names and "seg.seal" in names
    assert "seg.evacuate" in names and "seg.clean" in names
    seg_ev = [e for e in tr.events() if e["name"].startswith("seg.")]
    assert {e["tid"] for e in seg_ev} == {2}   # store lane
    ev = next(e for e in tr.events() if e["name"] == "seg.evacuate")
    assert {"seg", "E", "up2", "stream"} <= set(ev["args"])


# ------------------------------------------------------------------- wamp

def test_wamp_zero_writes_is_zero():
    assert StoreStats().wamp() == 0.0
    assert StoreStats(gc_moves=5).wamp() == 0.0          # the /1 leak, fixed
    assert StoreStats(gc_moves=5, user_writes=10).wamp() == 0.5
    # byte counters win when present
    assert StoreStats(gc_moves=5, user_writes=10, user_bytes=100,
                      gc_bytes=25).wamp() == 0.25


def test_per_stream_wamp():
    s = StoreStats(stream_writes=[4, 0, 2], stream_moves=[2, 1])
    assert s.per_stream_wamp() == [0.5, 0.0, 0.0]
    assert StoreStats().per_stream_wamp() == []


# ----------------------------------------------------------- metrics logger

def test_metrics_logger_deltas_and_flush():
    buf = io.StringIO()
    log = MetricsLogger(buf, clock=Tick())
    log.sample({"a": 10, "xs": [1, 2], "name": "mdc", "flag": True})
    log.sample({"a": 25, "xs": [2, 5], "name": "mdc", "flag": True})
    rows = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert [r["seq"] for r in rows] == [0, 1]
    assert rows[0]["d"] == {}                       # no previous sample
    assert rows[1]["d"] == {"a": 15, "xs": [1, 3]}  # numbers + lists only
    assert rows[1]["a"] == 25 and rows[1]["name"] == "mdc"
    assert rows[0]["t"] < rows[1]["t"]


def test_metrics_logger_owns_path(tmp_path):
    p = tmp_path / "m.jsonl"
    log = MetricsLogger(p, clock=Tick())
    log.sample({"a": 1})
    log.close()
    assert json.loads(p.read_text().splitlines()[0])["a"] == 1


# -------------------------------------------------------------- calibration

def test_calibration_counts_and_histogram():
    cal = DeathCalibration(n_streams=2, hist_bins=6)
    # lifetimes 0, 1, 2, 3, 4 → bins 0, 1, 2, 2, 3 (bin 0: life < 1;
    # bin i: 2^(i-1) <= life < 2^i; the lifetime projection stays far
    # below the cut here, so nothing misroutes)
    cal.record(streams=[0, 0, 0, 0, 0],
               est=[10.0, 10, 10, 10, 10], actual=10.0,
               wtime=[10.0, 9, 8, 7, 6], bounds=[100.0])
    assert cal.deaths.tolist() == [5, 0]
    assert cal.life_hist[0].tolist() == [1, 1, 2, 1, 0, 0]
    assert len(cal.hist_edges) == 6
    assert cal.misroute_rate() == 0.0
    rep = cal.report()
    assert rep["deaths"] == 5 and rep["unrouted"] == 0
    json.dumps(rep)
    assert "death calibration" in cal.format_report()


def test_calibration_misroute_and_unrouted():
    cal = DeathCalibration(n_streams=2)
    # cut at 20: item 0 died fast (projected death 10+2=12 < 20 → stream 0,
    # was placed in 0: correct); item 1 died fast too but sat in stream 1:
    # misroute; item 2 has no estimate (direct append): unrouted
    cal.record(streams=[0, 1, 0], est=[12.0, 12.0, np.nan], actual=10.0,
               wtime=[8.0, 8.0, 8.0], bounds=[20.0])
    assert cal.routable.tolist() == [1, 1]
    assert cal.misroutes.tolist() == [0, 1]
    assert cal.misroute_rate() == 0.5
    assert cal.unrouted == 1
    per = cal.report()["per_stream"]
    assert per[1]["misroute_rate"] == 1.0 and per[0]["misroute_rate"] == 0.0


def test_calibration_via_framelog_kill_path():
    log = FrameLog(8, 4, n_streams=2)
    cal = DeathCalibration(n_streams=2)
    log.enable_calibration(cal)
    log.place(np.arange(4),
              Placement(est_death=np.array([5.0, 6.0, 7.0, 8.0])))
    log.tick(4)
    log.kill_slots(np.array([0, 0]), np.array([0, 1]))
    assert int(cal.deaths.sum()) == 2 and cal.unrouted == 0
    # direct append carries no estimate → unrouted
    s = log.alloc()
    log.append(s, np.array([100]), np.zeros(1))
    log.kill_slots(np.array([s]), np.array([0]))
    assert cal.unrouted == 1


# ------------------------------------------------- engine (golden schemas)

@pytest.fixture(scope="module")
def smoke_model():
    import jax

    from repro.configs import get_config
    from repro.models import Model
    model = Model(get_config("qwen3-1.7b").smoke())
    return model, model.init(jax.random.PRNGKey(0))


# keys always present in engine.metrics(); feature-gated keys listed apart
GOLDEN_METRICS = {
    "blocks_written": int, "blocks_moved": int, "wamp": float,
    "mean_E_compacted": float, "compactions": int, "streams": int,
    "stream_writes": list, "stream_moves": list, "per_stream_wamp": list,
    "free_blocks": int, "preemptions": int, "resumes": int,
    "recomputed_tokens": int, "dispatches": int,
}


def test_engine_obs_end_to_end(smoke_model, tmp_path):
    """One instrumented engine drain checks the golden ``metrics()`` schema,
    the pluggable clock (admit_wall on the fake timebase), the exported
    trace (valid Chrome trace, spans nest, request lifecycle + segment
    events present), the per-dispatch phase attribution, the metrics JSONL
    time series, and the calibration report."""
    import jax.numpy as jnp

    from repro.serving import PagedServingEngine
    model, params = smoke_model
    clock = Tick()
    tracer = Tracer(capacity=1 << 14, clock=clock)
    mpath = tmp_path / "metrics.jsonl"
    eng = PagedServingEngine(
        model, n_slabs=8, blocks_per_slab=4, page_T=8, max_batch=3,
        max_seq=96, policy="mdc", params=params, compact_trigger=2,
        compact_batch=3, pool_dtype=jnp.float32, preemption=True,
        warmup=True, clock=clock, tracer=tracer, calibration=True,
        metrics_every=2, metrics_sink=mpath, phase_log=True)
    rng = np.random.default_rng(3)
    for _ in range(5):
        eng.submit(rng.integers(1, model.cfg.vocab_size,
                                size=int(rng.integers(4, 30))),
                   int(rng.integers(4, 12)))
    while eng.has_work():
        eng.step()
    eng.pool.check_invariants()

    # golden metrics schema (bool is an int subclass — exclude explicitly)
    m = eng.metrics()
    for k, t in GOLDEN_METRICS.items():
        assert k in m, f"metrics() lost key {k}"
        assert isinstance(m[k], t) and not isinstance(m[k], bool), (k, m[k])
    assert 0.0 <= m["misroute_rate"] <= 1.0
    assert len(m["per_stream_wamp"]) == m["streams"]
    json.dumps(m)

    # one clock: admission stamps sit on the fake timebase, not time.time()
    assert eng.clock is clock
    assert all(t >= 1000.0 for t in eng.admit_wall.values())

    # trace: schema-valid, both lifecycles present, stable lanes
    doc = tracer.export(tmp_path / "trace.json")
    _check_chrome_trace(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"step", "dispatch", "host_sync", "pool", "req",
            "req.admit", "seg.open", "seg.seal"} <= names
    req_ev = [e for e in doc["traceEvents"] if e.get("cat") == "request"]
    assert req_ev and {e["tid"] for e in req_ev} == {1}
    assert {e["tid"] for e in doc["traceEvents"]
            if e["name"].startswith("seg.")} == {2}

    # phase attribution: every dispatch produced a split that sums sanely
    pr = eng.phase_report()
    assert pr["dispatches"] == m["dispatches"] > 0
    assert pr["p99_ms"] >= pr["p50_ms"] > 0
    assert set(pr["phase_mean_ms"]) >= {"dispatch", "host_sync"}
    assert 0.0 <= pr["compaction_share_p99"] <= 1.0
    for row in eng.dispatch_phases:
        assert row["total"] >= 0
        assert sum(v for k, v in row.items() if k != "total") \
            <= row["total"] + 1e-9

    # metrics time series: sampled every 2 dispatches, deltas monotone
    rows = [json.loads(line) for line in mpath.read_text().splitlines()]
    assert len(rows) >= 2
    assert all(r["seq"] == i for i, r in enumerate(rows))
    assert all(r["d"].get("dispatches", 2) > 0 for r in rows[1:])
    assert {"u_now", "queue_depth", "active_slots"} <= set(rows[0])

    # calibration saw the pool's deaths
    rep = eng.calibration.report()
    assert rep["deaths"] > 0 and len(rep["per_stream"]) == eng.streams


def test_engine_obs_disabled_is_inert_and_identical(smoke_model):
    """The default engine carries no tracer/calibration state and produces
    byte-identical outputs and metrics to an instrumented run (obs must
    observe, never perturb)."""
    import jax.numpy as jnp

    from repro.serving import PagedServingEngine
    model, params = smoke_model
    kw = dict(n_slabs=8, blocks_per_slab=4, page_T=8, max_batch=3,
              max_seq=96, policy="mdc", params=params, compact_trigger=2,
              compact_batch=3, pool_dtype=jnp.float32, preemption=True,
              warmup=True)
    rng = np.random.default_rng(4)
    reqs = [(rng.integers(1, model.cfg.vocab_size,
                          size=int(rng.integers(4, 30))),
             int(rng.integers(4, 12))) for _ in range(4)]

    def run(**obs):
        eng = PagedServingEngine(model, **kw, **obs)
        rids = [eng.submit(p, n) for p, n in reqs]
        while eng.has_work():
            eng.step()
        return [eng.finished[r] for r in rids], eng

    plain_toks, plain = run()
    assert plain.tracer is None and plain.calibration is None
    assert plain.pool.core.tracer is None
    obs_toks, obs = run(tracer=Tracer(capacity=1 << 14, clock=Tick()),
                        calibration=True, phase_log=True)
    assert obs_toks == plain_toks, "observability changed decoded tokens"
    assert obs.metrics()["wamp"] == plain.metrics()["wamp"]
    assert obs.metrics()["blocks_moved"] == plain.metrics()["blocks_moved"]


# ------------------------------------- profiler spans (jax.profiler clock)

# the phases phase_report() splits a dispatch into
REPORT_PHASES = {"admit", "alloc", "upload", "dispatch", "host_sync",
                 "compaction", "journal"}


def test_span_records_tracer_pair_and_late_args():
    from repro.obs import span
    tr = Tracer(capacity=16, clock=Tick())
    with span("compact", "pool", tr, budget=4) as late:
        with span("compaction", "engine", tr):
            pass
        late["victims"] = 2
    with span("upload", "engine") as late:   # no tracer: profiler only
        assert late == {}
    _check_chrome_trace(tr.export())
    ev = tr.events()
    assert [(e["ph"], e["name"], e.get("cat")) for e in ev] == [
        ("B", "compact", "pool"), ("B", "compaction", "engine"),
        ("E", "compaction", None), ("E", "compact", None)]
    assert ev[0]["args"] == {"budget": 4}
    assert ev[-1]["args"] == {"victims": 2}


def _profiled_spans(trace_dir):
    """Every ``engine.*`` / ``pool.*`` host event of the profile in
    ``trace_dir`` as a dict with its interval, its stats and the name of
    the innermost such event around it on the same thread."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            evs = sorted(((e.start_ns, -e.duration_ns, e) for e in ln.events
                          if e.name.startswith(("engine.", "pool."))),
                         key=lambda x: x[:2])
            stack = []
            for a, negd, e in evs:
                b = a - negd
                while stack and stack[-1]["t1"] < b:
                    stack.pop()
                out.append(dict(name=e.name, t0=a, t1=b,
                                stats=dict(e.stats),
                                parent=stack[-1]["name"] if stack else None,
                                id=len(out),
                                pid=stack[-1]["id"] if stack else None))
                stack.append(out[-1])
    return out


@pytest.fixture(scope="module")
def profiled_run(smoke_model, tmp_path_factory):
    """The end-to-end test's geometry under pressure (one placement
    stream, longer requests, synchronous cleaning), drained while the JAX
    profiler records, with a tracer and phase rows on."""
    import jax
    import jax.numpy as jnp

    from repro.serving import PagedServingEngine
    model, params = smoke_model
    tracer = Tracer(capacity=1 << 16)
    eng = PagedServingEngine(
        model, n_slabs=8, blocks_per_slab=4, page_T=8, max_batch=3,
        max_seq=96, policy="mdc", params=params, compact_trigger=2,
        compact_batch=3, streams=1, pool_dtype=jnp.float32,
        preemption=True, warmup=True, tracer=tracer, phase_log=True)
    rng = np.random.default_rng(3)
    for _ in range(10):
        eng.submit(rng.integers(1, model.cfg.vocab_size,
                                size=int(rng.integers(20, 60))),
                   int(rng.integers(8, 30)))
    m0 = eng.metrics()
    tdir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(tdir))
    try:
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    m1 = eng.metrics()
    delta = {k: m1[k] - m0[k] for k in ("dispatches", "compactions",
                                        "blocks_moved")}
    return eng, tracer, _profiled_spans(tdir), delta


# the innermost engine/pool span each span may sit in (None: outermost)
PARENTS = {
    "engine.step": {None},
    "engine.admit": {"engine.step"},
    "engine.prefill": {"engine.admit"},
    "engine.alloc": {"engine.step", "engine.admit"},
    "engine.upload": {"engine.step"},
    "engine.dispatch": {"engine.step"},
    "engine.host_sync": {"engine.step", "engine.admit", "engine.emit"},
    "engine.emit": {"engine.step"},
    "pool.compact": {"engine.alloc"},
    "engine.compaction": {"pool.compact"},
}


def test_profiled_engine_spans_nest(profiled_run):
    eng, _, spans, delta = profiled_run
    assert delta["compactions"] > 0 and delta["blocks_moved"] > 0, \
        "the profiled run never cleaned"
    names = {s["name"] for s in spans}
    assert names == set(PARENTS), names
    for s in spans:
        assert s["parent"] in PARENTS[s["name"]], s
    # the step span carries its dispatch index
    steps = [s for s in spans if s["name"] == "engine.step"]
    idx = [s["stats"]["dispatch"] for s in steps]
    assert idx == sorted(idx) and idx[-1] == eng.dispatches - 1


def test_profiled_engine_span_counts_match_counters(profiled_run):
    eng, _, spans, delta = profiled_run
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["pid"], []).append(s["name"])
    steps = [s for s in spans if s["name"] == "engine.step"]
    dispatching = [s for s in steps if "engine.dispatch" in kids.get(
        s["id"], [])]
    assert len(dispatching) == delta["dispatches"]
    # every cycle gets a span; those that cleaned a slab are the
    # compactions, and the rest found no victim and moved nothing
    cycles = [s for s in spans if s["name"] == "pool.compact"]
    cleaned = [s for s in cycles if s["stats"]["victims"] > 0]
    assert len(cleaned) == delta["compactions"]
    assert sum(s["stats"]["moves"] for s in cycles) == delta["blocks_moved"]
    assert all(s["stats"]["moves"] == 0 for s in cycles
               if s["stats"]["victims"] == 0)
    # synchronous cleaning: the engine's move and remap run inside the
    # cycle that planned them, once per cycle with moves
    moved = [s for s in cycles if s["stats"]["moves"] > 0]
    assert sorted(s["pid"] for s in spans
                  if s["name"] == "engine.compaction") \
        == sorted(s["id"] for s in moved)
    # an admission that prefilled waited for the prefill's first token
    admits = [s for s in spans if s["name"] == "engine.admit"]
    prefilled = [a for a in admits if "engine.prefill" in kids.get(
        a["id"], [])]
    assert prefilled
    for a in prefilled:
        k = kids[a["id"]]
        assert k.count("engine.host_sync") == k.count("engine.prefill")


def test_profiled_engine_tracer_names_and_phase_keys(profiled_run):
    """The tracer records the same spans under the profiler's names less
    the category, and ``phase_report()`` splits dispatches into the same
    phases as before the profiler spans."""
    eng, tracer, spans, _ = profiled_run
    begins = [e for e in tracer.events() if e["ph"] == "B"]
    assert sorted(f"{e['cat']}.{e['name']}" for e in begins) \
        == sorted(s["name"] for s in spans)
    _check_chrome_trace(tracer.export())
    assert {"step", "admit", "upload", "dispatch", "host_sync",
            "compaction"} <= {e["name"] for e in begins}
    pr = eng.phase_report()
    assert set(pr) == {"dispatches", "p50_ms", "p99_ms", "phase_mean_ms",
                       "phase_share_p99_tail", "compaction_share_p99",
                       "compaction_share_total"}
    assert {"admit", "upload", "dispatch", "host_sync", "compaction"} \
        <= set(pr["phase_mean_ms"]) <= REPORT_PHASES
    assert pr["dispatches"] == eng.dispatches
