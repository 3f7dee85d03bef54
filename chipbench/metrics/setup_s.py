"""Process start to window open, less the warm-up traffic: loading,
weights, engine construction and its compiles (host clock)."""


def read(run):
    return run.setup_s
