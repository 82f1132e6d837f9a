"""How many of the experts held here a decode run has to read: over the
window's ``engine.tick`` spans that decoded, the mean of ``experts_hit``
((layer, held expert) pairs that got at least one of the run's rows) over
``expert layers x experts_held``. Every held expert that is hit costs its
two matrices whatever the rows, so at the deployment's batch the share is
near 100 and the expert layers' time is the held weights; a sparser batch,
or a router taught to cluster, lowers it. A program whose ticks carry no
such count, or a run whose records carry no ``nemotron_h`` sizes (another
runner's), gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    cfg = run["records"].get("nemotron_h")
    w = program_spans.serve_window(run)
    if cfg is None or w is None or any(
            "experts_hit" not in t.attrs for t in w.ticks):
        return None
    hit = [t.attrs["experts_hit"] for t in w.ticks if t.attrs["decoding"]]
    if not hit:
        return None
    return 100.0 * statistics.fmean(hit) / (cfg["pattern"].count("E")
                                            * cfg["experts_held"])
