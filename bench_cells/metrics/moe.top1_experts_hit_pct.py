"""How many of the experts a decode run of a top-1 mixture has to read: over
the window's ``engine.tick`` spans that decoded, the mean of ``experts_hit``
((layer, expert) pairs that got at least one of the run's rows) over
``n_layers x n_experts``. One expert a token: a run of ``n`` rows hits ``E
(1 - (1 - 1/E)^n)`` of a layer's ``E`` by chance (12.6 of 16 at 24 rows),
and every one it hits costs its three matrices whatever the rows. Reads the
records' ``zaya`` sizes; a run whose records carry none (another runner's),
or a program whose ticks carry no such count, gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    cfg = run["records"].get("zaya")
    w = program_spans.serve_window(run)
    if cfg is None or w is None or any(
            "experts_hit" not in t.attrs for t in w.ticks):
        return None
    hit = [t.attrs["experts_hit"] for t in w.ticks if t.attrs["decoding"]]
    if not hit:
        return None
    return 100.0 * statistics.fmean(hit) / (cfg["n_layers"]
                                            * cfg["n_experts"])
