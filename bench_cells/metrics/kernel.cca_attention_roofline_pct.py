"""The latent attention's memory-bound roofline share inside the decode
tick's program: the bytes it has to move (``bench_cells/counts_zaya.py::
latent_kv_bytes``: every layer's K and V row of each cached position the
run's slots hold, at the pool's own width of ``n_kv_heads x head_dim``
lanes, the queries in and the outputs out) over the chip's HBM bandwidth,
divided by the summed device time of the paged-attention kernel's events
inside the decode program's runs (``bench_cells/decode_runs.py``). The
positions are the program's own count, ``kv_positions`` on every
``engine.tick`` span; ``kernel.paged_attention_roofline_pct`` counts a GPT
row (``n_heads x head_dim`` of the model's width) and the harness's own
lengths, which is not this pool's. Reads the records' ``zaya`` sizes; a run
whose records carry none (another runner's), or a program whose ticks carry
no ``kv_positions``, gives nothing."""

from bench_cells import counts_zaya, decode_runs


def read(run):
    found = decode_runs.traced_decode_ticks(run, "zaya", ("kv_positions",))
    if found is None:
        return None
    cfg, ticks = found
    r = run["records"]
    return decode_runs.roofline_pct(run, "paged_attention", [
        counts_zaya.latent_kv_bytes(cfg, t["kv_positions"], r["n_slots"],
                                    r["cache_itemsize"])
        for t in ticks])
