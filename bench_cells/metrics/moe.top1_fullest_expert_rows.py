"""The rows the fullest expert of a decode run multiplies: over the window's
``engine.tick`` spans that decoded, the mean of ``expert_rows_max`` (the
most rows one expert got in any layer of the run). With one expert a token
and 24 rows over 16 experts chance gives the fullest of a layer 4 to 5, the
fullest of 20 layers 6 to 7; a router that sends a tick's rows one way reads
24. Reads the records' ``zaya`` sizes; a run whose records carry none
(another runner's), or a program whose ticks carry no such count, gives
nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    w = program_spans.serve_window(run)
    if run["records"].get("zaya") is None or w is None or any(
            "expert_rows_max" not in t.attrs for t in w.ticks):
        return None
    most = [t.attrs["expert_rows_max"] for t in w.ticks
            if t.attrs["decoding"]]
    return statistics.fmean(most) if most else None
