"""How many of the routed experts held here (a sixteenth of every mixture
layer's) a decode run has to read: over the window's ``engine.tick`` spans
that decoded, the mean of ``experts_hit`` ((layer, held expert) pairs that
got at least one of the run's LIVE rows) over ``mixture layers x
experts_held``. Top 8 of 256 over 64 rows gives a held expert two rows by
chance and hits ``1 - (31/32)^64`` = 86.9 % of them; every one it hits costs
its three matrices whatever the rows. Reads the records' ``kimi_linear``
sizes; a run whose records carry none (another runner's), or a program
whose ticks carry no such count, gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    cfg = run["records"].get("kimi_linear")
    w = program_spans.serve_window(run)
    if cfg is None or w is None or any(
            "experts_hit" not in t.attrs for t in w.ticks):
        return None
    hit = [t.attrs["experts_hit"] for t in w.ticks if t.attrs["decoding"]]
    if not hit:
        return None
    return 100.0 * statistics.fmean(hit) / (
        (cfg["n_layers"] - cfg["n_dense"]) * cfg["experts_held"])
