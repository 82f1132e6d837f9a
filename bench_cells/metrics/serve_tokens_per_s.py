"""All output tokens that requests received inside the window over the
whole window."""

from bench_cells import readings


def read(run):
    r = run["records"]
    if r.get("kind") != "serve":
        return None
    return readings.tokens_received(r) / r["window_s"]
