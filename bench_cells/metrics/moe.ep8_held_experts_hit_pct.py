"""How many of the routed experts held here (an eighth of every layer's) a
decode run has to read: over the window's ``engine.tick`` spans that
decoded, the mean of ``experts_hit`` ((layer, held expert) pairs that got at
least one of the run's rows) over ``n_layers x experts_held``. Top 8 of 128
over 16 rows gives a held expert one row by chance and hits ``16 (1 -
(15/16)^16)`` = 10.3 of 16; every one it hits costs its three matrices
whatever the rows. Reads the records' ``cohere2`` sizes; a run whose records
carry none (another runner's), or a program whose ticks carry no such count,
gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    cfg = run["records"].get("cohere2")
    w = program_spans.serve_window(run)
    if cfg is None or w is None or any(
            "experts_hit" not in t.attrs for t in w.ticks):
        return None
    hit = [t.attrs["experts_hit"] for t in w.ticks if t.attrs["decoding"]]
    if not hit:
        return None
    return 100.0 * statistics.fmean(hit) / (cfg["n_layers"]
                                            * cfg["experts_held"])
