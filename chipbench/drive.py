"""The measured window: drives ``PagedServingEngine.submit`` / ``.step``
under a closed or an open loop and records, per request and per dispatch,
what the end-to-end and per-layer metrics are computed from.

The open-loop arithmetic is that of ``repro.launch.serve._open_loop``,
copied here so the yardstick does not move with the program: arrivals are
scheduled ahead of time, independent of completions; TTFT runs from the
*scheduled* arrival, so a stalled loop prices its delay into later
requests; and each request's submit time is recorded beside its scheduled
arrival, on the engine's own clock.

Work accounting is per dispatch and per request: a request's first output
token comes from its prefill (``plen`` prompt tokens), and its k-th token
(k >= 2) from a decode iteration that attends over ``plen + k - 1``
positions.  Which requests had pages relocated by the cleaner is read off
the engine's block table: a live row whose existing page ids changed
across a dispatch was compacted.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import numpy as np

from .traffic import Requests

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# open loop: how long after the window closes the run waits for the first
# token of requests that arrived in it
DRAIN_S = 30.0


@dataclasses.dataclass
class Req:
    plen: int
    n_out: int
    arrival: float           # scheduled (open loop) or submit time (closed)
    submit: float = 0.0
    first: float | None = None
    last: float | None = None   # the latest dispatch that emitted tokens
    done: float | None = None
    prompt: np.ndarray | None = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    tokens: int              # output tokens emitted by this dispatch
    prefill: list            # prompt lengths prefilled
    decode: list             # attention lengths of the decode iterations
    # per request that had emitted before: (seconds since its previous
    # emission / tokens emitted now, tokens emitted now)
    gaps: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    steps: list = dataclasses.field(default_factory=list)
    counters0: dict = dataclasses.field(default_factory=dict)
    counters1: dict = dataclasses.field(default_factory=dict)


class Driver:
    def __init__(self, eng, mix: dict, seed: int, rate: float = 0.0):
        self.eng = eng
        self.mix = mix
        self.rate = rate
        self.reqs = Requests(mix, seed, eng.cfg.vocab_size, rate)
        self.clock = eng.clock
        self.by_rid: dict[int, Req] = {}
        self.moved: set[int] = set()
        self.all_steps: list[Step] = []
        self.win = Window()
        self._in_window = False
        # programs lowered while the window is open: a compile (or a
        # compile-cache load) inside the window, which set-up should cover
        self.window_compiles = 0
        # Python's garbage collector inside the window: seconds in all and
        # the longest pause (a stall inside step() with the device idle)
        self.window_gc_s = 0.0
        self.window_gc_max_s = 0.0
        self._gc_t0 = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self._in_window and event == LOWERED:
            self.window_compiles += 1

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._in_window and self._gc_t0 is not None:
            dt = time.perf_counter() - self._gc_t0
            self.window_gc_s += dt
            self.window_gc_max_s = max(self.window_gc_max_s, dt)

    # ----------------------------------------------------------- loops
    def _submit(self, now: float, arrival: float) -> int:
        prompt, n_out, _ = self._pending
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = self.eng.submit(prompt, n_out)
        self.by_rid[rid] = Req(len(prompt), n_out, arrival, submit=now,
                               prompt=prompt)
        return rid

    def _draw(self):
        self._pending = self.reqs.next()
        return self._pending

    def _counters(self) -> dict:
        m = self.eng.metrics()
        c = {k: m[k] for k in ("blocks_written", "blocks_moved",
                               "compactions", "dispatches")}
        c["queued"] = len(self.eng.queue)
        return c

    def _step(self) -> list[int]:
        eng = self.eng
        live = eng.rid >= 0
        before = {int(r): int(n) for r, n in zip(eng.rid[live],
                                                 eng._out_n[live])}
        rows = {int(eng.rid[i]): eng.bt[i, :eng.npages[i]].copy()
                for i in np.flatnonzero(live)}
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            done = eng.step()
        t1 = self.clock()
        after = {int(r): int(n) for r, n in zip(eng.rid[eng.rid >= 0],
                                                 eng._out_n[eng.rid >= 0])}
        for rid in done:
            after[int(rid)] = len(eng.finished[rid])
        st = Step(t0, t1, 0, [], [])
        for rid, n1 in after.items():
            n0 = before.get(rid, 0)
            r = self.by_rid.get(rid)
            if r is None:  # sent by an earlier driver of this engine
                continue
            if n0 == 0 and n1 > 0:
                st.prefill.append(r.plen)
                r.first = t1
            elif n1 > n0:
                st.gaps.append(((t1 - r.last) / (n1 - n0), n1 - n0))
            if n1 > n0:
                r.last = t1
            st.decode.extend(r.plen + k - 1 for k in range(max(n0, 1) + 1,
                                                           n1 + 1))
            st.tokens += n1 - n0
        for i in np.flatnonzero(eng.rid >= 0):
            rid = int(eng.rid[i])
            old = rows.get(rid)
            if old is not None and not np.array_equal(
                    old, eng.bt[i, :len(old)]):
                self.moved.add(rid)
        for rid in done:
            if int(rid) in self.by_rid:
                self.by_rid[int(rid)].done = t1
        self.all_steps.append(st)
        if self._in_window:
            self.win.steps.append(st)
        return done

    def run(self, warmup_s: float, seconds: float,
            trace_dir: str | None) -> Window:
        """Warm-up traffic for ``warmup_s``, then the window of
        ``seconds`` (traced into ``trace_dir`` when given)."""
        gc.callbacks.append(self._on_gc)
        try:
            if self.mix["loop"] == "closed":
                return self._closed(warmup_s, seconds, trace_dir)
            return self._open(warmup_s, seconds, trace_dir)
        finally:
            gc.callbacks.remove(self._on_gc)

    def _open_window(self, trace_dir):
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            self._win_ann = jax.profiler.TraceAnnotation("bench.window")
            self._win_ann.__enter__()
        self.win.counters0 = self._counters()
        self.win.t0 = self.clock()
        self._in_window = True

    def _close_window(self, trace_dir):
        self._in_window = False
        self.win.t1 = self.clock()
        self.win.counters1 = self._counters()
        if trace_dir:
            self._win_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def _closed(self, warmup_s, seconds, trace_dir) -> Window:
        clients = int(self.mix["clients"])
        t_start = self.clock()
        for _ in range(clients):
            self._draw()
            now = self.clock()
            self._submit(now, now)
        opened = False
        while True:
            now = self.clock()
            if not opened and now - t_start >= warmup_s:
                self._open_window(trace_dir)
                opened = True
            if opened and now - self.win.t0 >= seconds:
                break
            done = self._step()
            for _ in done:   # each client sends its next request at once
                self._draw()
                now = self.clock()
                self._submit(now, now)
        self._close_window(trace_dir)
        return self.win

    def _open(self, warmup_s, seconds, trace_dir) -> Window:
        eng = self.eng
        t_start = self.clock()
        self._draw()
        nxt = t_start + self._pending[2]
        opened = closed = False
        while True:
            now = self.clock()
            if not opened and now - t_start >= warmup_s:
                self._open_window(trace_dir)
                opened = True
            if opened and not closed and now - self.win.t0 >= seconds:
                self._close_window(trace_dir)
                closed = True
            if closed:
                waiting = [r for r in self.by_rid.values()
                           if self.win.t0 <= r.arrival < self.win.t1
                           and r.first is None]
                if not waiting or now - self.win.t1 >= DRAIN_S:
                    break
            while nxt <= now:
                self._submit(now, nxt)
                self._draw()
                nxt += self._pending[2]
            if not eng.has_work():
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(min(nxt - self.clock(), 2e-3), 0.0))
                continue
            self._step()
        self.end = self.clock()
        return self.win
