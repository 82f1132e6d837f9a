"""Seconds JAX spent tracing the cell's programs and lowering them to
StableHLO during set-up (its own monitoring events): the part of set-up
that no compile cache saves."""


def read(run):
    s = run["setup"]
    return s["trace_s"] + s["lower_s"]
