"""p90 over every output token emitted in the window after its request's
first token, of the time since that request's previous emission over the
tokens emitted with it (host clock)."""
from chipbench.measure import p90


def read(run):
    v = p90(run.tpot_s())
    return None if v is None else v * 1e3
