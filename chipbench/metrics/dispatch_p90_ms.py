"""Dispatch: p90 of the wall time of the window's ``step()`` calls that
dispatched work (host clock)."""
from chipbench.measure import p90


def read(run):
    v = p90([s.t1 - s.t0 for s in run.steps if s.tokens])
    return None if v is None else v * 1e3
