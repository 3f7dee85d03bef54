"""Observability for the log-structured store and serving engine.

Three small, dependency-free pieces (DESIGN.md §12):

- :mod:`repro.obs.trace` — bounded-ring structured event tracer with
  Chrome-trace / Perfetto JSON export, and ``span``: the engine's and the
  pool's phase spans, on the JAX profiler's clock always and in a tracer
  when one is attached.  The core emits segment-lifecycle events, the
  engine emits request spans and per-dispatch phase spans.
- :mod:`repro.obs.metrics` — periodic JSONL snapshots with per-interval
  deltas (Wamp, u_now, free blocks, per-stream writes/moves, queue depth).
- :mod:`repro.obs.calibration` — est-death vs. actual-death recording at
  kill time: per-stream misroute rate and death-time histograms, i.e. the
  observed death distribution stream auto-tuning needs.

Everything else is opt-in: with no tracer/calibration attached the hot
paths run a single ``is None`` check besides the profiler annotations,
which cost about a null context while no profile runs.
"""

from .calibration import DeathCalibration
from .metrics import MetricsLogger
from .trace import Tracer, span

__all__ = ["DeathCalibration", "MetricsLogger", "Tracer", "span"]
