"""The top-1 expert products' memory-bound roofline share inside the decode
tick's program: the bytes they have to move (``bench_cells/counts_zaya.py::
top1_experts_bytes``: each hit expert's three matrices once a run, every
slot's row in and its result out a layer) over the chip's HBM bandwidth,
divided by the summed device time of the operations that do them
(``bench_cells/decode_runs.py``: ``kernels.moe_experts`` in the traffic mix,
inside the decode program's runs only). The count is of the work, whatever
implements it. Reads the records' ``zaya`` sizes and the ticks'
``experts_hit``; a run whose records carry none (another runner's), or a
program whose ticks carry no such count, gives nothing."""

from bench_cells import counts_zaya, decode_runs


def read(run):
    found = decode_runs.traced_decode_ticks(run, "zaya", ("experts_hit",))
    if found is None:
        return None
    cfg, ticks = found
    rows = cfg["n_layers"] * run["records"]["n_slots"]
    return decode_runs.roofline_pct(run, "moe_experts", [
        counts_zaya.top1_experts_bytes(cfg, t["experts_hit"], rows)
        for t in ticks])
