"""Dispatch: output tokens emitted in the window over the engine's
dispatches in the window."""


def read(run):
    n = run.counter("dispatches")
    return run.tokens / n if n else None
