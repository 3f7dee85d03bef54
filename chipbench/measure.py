"""What a metric reader sees of a run: the window's requests and
dispatches, the engine's counters at the window's edges, the trace's
reduction and the peaks of the device.  A reader is a module
``chipbench/metrics/<metric name>.py`` with ``read(run) -> float | None``;
it returns None where the run gives it nothing to read."""

from __future__ import annotations

import dataclasses

import numpy as np

from . import work


def p90(xs) -> float | None:
    return float(np.percentile(np.asarray(xs, float), 90)) if len(xs) else None


@dataclasses.dataclass
class Run:
    cfg: dict                 # the configuration file
    mix: dict                 # the traffic file
    peak: dict                # the device's row of peaks.json
    setup_s: float
    driver: object            # drive.Driver after the window
    trace: dict | None = None  # trace.reduce_trace(), --trace 1 only

    @property
    def win(self):
        return self.driver.win

    @property
    def window_s(self) -> float:
        return self.win.t1 - self.win.t0

    @property
    def steps(self) -> list:
        return self.win.steps

    @property
    def tokens(self) -> int:
        return sum(s.tokens for s in self.steps)

    def counter(self, name: str) -> int:
        return self.win.counters1[name] - self.win.counters0[name]

    @property
    def open_loop(self) -> bool:
        return self.mix["loop"] == "open"

    def window_arrivals(self) -> list:
        w = self.win
        return [r for r in self.driver.by_rid.values()
                if w.t0 <= r.arrival < w.t1]

    def ttft_s(self) -> list[float]:
        """Open loop: every request scheduled in the window, from its
        scheduled arrival to its first token (or, for one that had none
        when the run stopped waiting, to that moment)."""
        end = getattr(self.driver, "end", self.win.t1)
        return [(r.first if r.first is not None else end) - r.arrival
                for r in self.window_arrivals()]

    def tpot_s(self) -> np.ndarray:
        """One value per output token emitted in the window after its
        request's first token: the seconds since that request's previous
        emission over the tokens it emitted then."""
        g = [x for s in self.steps for x in s.gaps]
        return np.repeat([x for x, _ in g], [k for _, k in g])

    def decode_lens(self) -> list[int]:
        return [c for s in self.steps for c in s.decode]

    def prefills(self) -> list[int]:
        return [p for s in self.steps for p in s.prefill]

    def model_flops(self) -> float:
        return work.model_flops(self.cfg, self.prefills(), self.decode_lens())

    def kernel_s(self, kernel: str) -> float | None:
        if self.trace is None:
            return None
        t = self.trace["kernel_s"].get(kernel, 0.0)
        return t if t > 0 else None
