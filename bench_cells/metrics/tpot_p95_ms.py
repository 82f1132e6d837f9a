"""95th percentile of every gap between consecutive output tokens of every
request in the window."""

from bench_cells import readings


def read(run):
    r = run["records"]
    if r.get("kind") != "serve":
        return None
    return 1e3 * readings.percentile(readings.token_gaps_s(r), 95)
