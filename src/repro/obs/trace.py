"""Low-overhead structured event tracer with Chrome-trace JSON export.

Events go into a bounded ring buffer (oldest dropped first, capacity never
exceeded); :meth:`Tracer.export` writes the Chrome trace-event JSON format
(``{"traceEvents": [...]}``) that loads in Perfetto / ``chrome://tracing``.

The tracer is *opt-in*: code that may run without one guards with
``if tracer is not None`` so the disabled path costs a single attribute
check.  When enabled, recording one event is a clock read plus a deque
append of a small tuple — no string formatting, no allocation beyond the
args dict the caller already built.

Timestamps come from a pluggable monotonic clock (default
``time.perf_counter``) shared with the engine, so queue-wait, compute
splits and trace spans sit on one timebase.

:class:`span` is the one way the engine and the pool open a span: it always
enters a ``jax.profiler.TraceAnnotation`` named ``<cat>.<name>``, which lands
in any running JAX profile on the same clock as the device ops, and records
the ``B``/``E`` pair in a :class:`Tracer` only when one is given.
"""

from __future__ import annotations

import json
import time
from collections import deque
from contextlib import contextmanager

__all__ = ["Tracer", "span"]

# event tuple layout: (ph, ts, tid, name, cat, args[, id])
_PH_BEGIN = "B"
_PH_END = "E"
_PH_INSTANT = "i"
_PH_COUNTER = "C"
_PH_ASYNC_BEGIN = "b"
_PH_ASYNC_INSTANT = "n"
_PH_ASYNC_END = "e"


class Tracer:
    """Bounded-ring event recorder with span / instant / counter API.

    ``capacity`` bounds memory: the ring holds at most that many events and
    drops the oldest first (``dropped`` counts them).  ``pid``/``tid`` map
    to Chrome-trace process/thread lanes; the engine uses tid 0 for the
    dispatch loop, tid 1 for request lifecycles and tid 2 for the store.
    """

    def __init__(self, capacity: int = 65536, clock=None, pid: int = 0):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.clock = clock if clock is not None else time.perf_counter
        self.pid = int(pid)
        self.dropped = 0
        self._buf: deque = deque(maxlen=self.capacity)
        self._t0 = self.clock()

    # -- recording ------------------------------------------------------------
    def _push(self, ev: tuple) -> None:
        if len(self._buf) == self.capacity:
            self.dropped += 1
        self._buf.append(ev)

    def instant(self, name: str, tid: int = 0, cat: str = "",
                **args) -> None:
        """One point-in-time event (Chrome-trace ph "i")."""
        self._push((_PH_INSTANT, self.clock(), tid, name, cat, args or None))

    def begin(self, name: str, tid: int = 0, cat: str = "", **args) -> None:
        """Open a duration span (ph "B"); close with :meth:`end`.  Spans on
        one tid must nest (close in reverse open order)."""
        self._push((_PH_BEGIN, self.clock(), tid, name, cat, args or None))

    def end(self, name: str, tid: int = 0, **args) -> None:
        self._push((_PH_END, self.clock(), tid, name, "", args or None))

    @contextmanager
    def span(self, name: str, tid: int = 0, cat: str = "", **args):
        self.begin(name, tid=tid, cat=cat, **args)
        try:
            yield self
        finally:
            self.end(name, tid=tid)

    def counter(self, name: str, tid: int = 0, **values) -> None:
        """Time-series sample (ph "C"): Perfetto renders one track per key."""
        self._push((_PH_COUNTER, self.clock(), tid, name, "", values))

    # -- async spans (overlapping lifecycles, e.g. one per request) -----------
    def async_begin(self, name: str, id: int, tid: int = 0,
                    cat: str = "async", **args) -> None:
        """Open an async span (ph "b"): spans with one (cat, id) pair form a
        track of their own, so overlapping requests need no nesting."""
        self._push((_PH_ASYNC_BEGIN, self.clock(), tid, name, cat,
                    args or None, int(id)))

    def async_instant(self, name: str, id: int, tid: int = 0,
                      cat: str = "async", **args) -> None:
        self._push((_PH_ASYNC_INSTANT, self.clock(), tid, name, cat,
                    args or None, int(id)))

    def async_end(self, name: str, id: int, tid: int = 0,
                  cat: str = "async", **args) -> None:
        self._push((_PH_ASYNC_END, self.clock(), tid, name, cat,
                    args or None, int(id)))

    # -- inspection / export --------------------------------------------------
    def __len__(self) -> int:
        return len(self._buf)

    def events(self) -> list[dict]:
        """Ring contents (oldest first) as Chrome-trace event dicts.
        ``ts`` is microseconds relative to tracer construction."""
        out = []
        for rec in self._buf:
            ph, ts, tid, name, cat, args = rec[:6]
            ev = {"ph": ph, "ts": (ts - self._t0) * 1e6,
                  "pid": self.pid, "tid": int(tid), "name": name}
            if cat:
                ev["cat"] = cat
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            if len(rec) > 6:
                ev["id"] = rec[6]
            out.append(ev)
        return out

    def export(self, path=None) -> dict:
        """Build (and optionally write) the Chrome-trace JSON document."""
        doc = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


_annotation = None  # jax.profiler.TraceAnnotation, imported at first use


class span:
    """``with span(name, cat, tracer, **args) as late:`` — a host span
    named ``<cat>.<name>`` on the JAX profiler's timeline (``args`` become
    the event's stats there) and, with ``tracer``, the same span as a
    ``name``/``cat`` ``B``/``E`` pair on its tid 0.

    ``late`` is a dict: what the body puts in it is attached to the span as
    it closes (values known only at the end, such as a cleaning cycle's
    victims).  With no profile running and no tracer, entering and leaving
    costs about a microsecond.  JAX is imported at the first span, so the
    simulator and the checkpoint store keep using :class:`Tracer` without
    touching a device."""

    __slots__ = ("name", "cat", "tracer", "args", "late", "_ann")

    def __init__(self, name: str, cat: str, tracer: Tracer | None = None,
                 **args):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation as _annotation
        self._ann = _annotation(f"{cat}.{name}", **args)
        self.name, self.cat, self.tracer, self.args = name, cat, tracer, args
        self.late: dict = {}

    def __enter__(self) -> dict:
        self._ann.__enter__()
        if self.tracer is not None:
            self.tracer.begin(self.name, cat=self.cat, **self.args)
        return self.late

    def __exit__(self, *exc) -> None:
        if self.late:
            self._ann.set_metadata(**self.late)
        if self.tracer is not None:
            self.tracer.end(self.name, **self.late)
        self._ann.__exit__(*exc)


def _jsonable(v):
    """Coerce numpy scalars/arrays so ``json.dump`` never chokes."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
        return v.item()
    if hasattr(v, "tolist"):
        return v.tolist()
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return str(v)
