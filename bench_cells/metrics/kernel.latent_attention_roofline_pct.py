"""The memory-bound roofline share of attention over an ABSORBED latent cache
inside the decode tick's program: the bytes it has to move
(``bench_cells/counts_kimi_linear.py::latent_kv_bytes``: per latent layer
the ONE row AS HELD of each cached position the run's slots hold, ONCE, the
absorbed queries in and the latent-wide outputs out) over the chip's HBM
bandwidth, divided by the summed device time of the paged-attention kernel's
events inside the decode program's runs (``bench_cells/decode_runs.py``). The
positions are the program's own count, ``kv_positions`` on every
``engine.tick`` span. A kernel that copied the row twice, as keys and as
values, moved twice what is counted here and reads half. Reads the records'
``kimi_linear`` sizes; a run whose records carry none (another runner's), or
a program whose ticks carry no such count, gives nothing."""

from bench_cells import counts_kimi_linear, decode_runs


def read(run):
    found = decode_runs.traced_decode_ticks(run, "kimi_linear",
                                            ("kv_positions",))
    if found is None:
        return None
    cfg, ticks = found
    r = run["records"]
    return decode_runs.roofline_pct(run, "paged_attention", [
        counts_kimi_linear.latent_kv_bytes(cfg, t["kv_positions"],
                                           r["n_slots"], r["cache_itemsize"])
        for t in ticks])
