"""Submit to first token, median over the window's requests."""

from bench_cells import readings


def read(run):
    r = run["records"]
    if r.get("kind") != "serve":
        return None
    return 1e3 * readings.percentile(readings.ttfts_s(r), 50)
