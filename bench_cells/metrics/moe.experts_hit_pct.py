"""How many of the experts a decode run has to read: over the window's
``engine.tick`` spans that ran a block forward, the mean of ``experts_hit``
((layer, expert) pairs that got at least one of the run's rows) over
``n_layers x n_experts``. Every expert that is hit costs its three matrices
whatever the rows, so at a full batch the share is near 100 and the tick is
the experts' weights; a sparser batch, or a router taught to cluster, lowers
it. A program whose ticks carry no such count, or a run whose records carry
no ``sdar`` sizes (another runner's), gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    cfg = run["records"].get("sdar")
    w = program_spans.serve_window(run)
    if cfg is None or w is None or any(
            "experts_hit" not in t.attrs for t in w.ticks):
        return None
    hit = [t.attrs["experts_hit"] for t in w.ticks if t.attrs["forwards"]]
    if not hit:
        return None
    return 100.0 * statistics.fmean(hit) / (cfg["n_layers"]
                                            * cfg["n_experts"])
