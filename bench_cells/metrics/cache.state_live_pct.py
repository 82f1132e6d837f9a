"""How much of the recurrent state the traffic keeps in use: the mean over
the window's ``engine.tick`` spans of ``state_slots`` (slots whose state is
live at the end of the tick, prefilling ones included) over ``n_slots``. A
program whose ticks carry no such count gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    w = program_spans.serve_window(run)
    if w is None or any("state_slots" not in t.attrs for t in w.ticks):
        return None
    return 100.0 * statistics.fmean(
        t.attrs["state_slots"] for t in w.ticks) / run["records"]["n_slots"]
