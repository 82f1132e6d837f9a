"""How many rows a hit held expert multiplies in a decode run: over the
window's ``engine.tick`` spans that decoded, ``expert_rows`` ((token,
expert) pairs that landed on an expert held here) over ``experts_hit``,
summed over the expert layers, as a mean over those ticks. With every chip
seeing every token, ``n_slots x top_k / n_experts`` (96 x 22 / 512 = 4.1) is
what the deployment gives each expert; fewer rows an expert read the same
weights for less. A program whose ticks carry no such counts gives
nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    w = program_spans.serve_window(run)
    if w is None or run["records"].get("nemotron_h") is None or any(
            "expert_rows" not in t.attrs for t in w.ticks):
        return None
    each = [t.attrs["expert_rows"] / t.attrs["experts_hit"]
            for t in w.ticks if t.attrs["decoding"]
            and t.attrs["experts_hit"]]
    return statistics.fmean(each) if each else None
