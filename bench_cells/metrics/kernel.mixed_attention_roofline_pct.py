"""The memory-bound roofline share of attention over a pool of two layer
kinds inside the decode tick's program: the bytes it has to move
(``bench_cells/counts_cohere2.py::kv_bytes``: a full layer's K and V row of
each cached position the run's slots hold, a window layer's of the positions
inside the window alone, the queries in and the outputs out) over the
chip's HBM bandwidth, divided by the summed device time of the
paged-attention kernel's events inside the decode program's runs
(``bench_cells/decode_runs.py``). The positions are the program's own
counts, ``kv_positions`` and ``kv_window_positions`` on every
``engine.tick`` span: a window layer that fetched what lies behind its
window moved more than is counted here and reads LOW. Reads the records'
``cohere2`` sizes; a run whose records carry none (another runner's), or a
program whose ticks carry no such counts, gives nothing."""

from bench_cells import counts_cohere2, decode_runs


def read(run):
    found = decode_runs.traced_decode_ticks(
        run, "cohere2", ("kv_positions", "kv_window_positions"))
    if found is None:
        return None
    cfg, ticks = found
    r = run["records"]
    return decode_runs.roofline_pct(run, "paged_attention", [
        counts_cohere2.kv_bytes(cfg, t["kv_positions"],
                                t["kv_window_positions"], r["n_slots"],
                                r["cache_itemsize"])
        for t in ticks])
