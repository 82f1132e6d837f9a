"""Median of the harness's own span around ``InferenceEngine.step()``."""

from bench_cells import readings


def read(run):
    r = run["records"]
    if r.get("kind") != "serve":
        return None
    return 1e3 * readings.percentile([te - ts for ts, te, _ in r["ticks"]],
                                     50)
