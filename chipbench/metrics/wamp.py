"""Pool and cleaner: blocks moved over blocks written in the window, from
the engine's counters (``metrics()``)."""


def read(run):
    w = run.counter("blocks_written")
    return run.counter("blocks_moved") / w if w else None
