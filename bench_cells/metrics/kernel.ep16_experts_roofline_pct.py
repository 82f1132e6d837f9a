"""The held routed experts' memory-bound roofline share inside the decode
tick's program, where a chip holds a sixteenth of every layer's experts: the
bytes their products have to move (``bench_cells/counts_kimi_linear.py::
held_experts_bytes``: each hit held expert's three matrices once a run, the
rows that landed on a held expert in and their results out) over the chip's
HBM bandwidth, divided by the summed device time of the operations that do
them (``bench_cells/decode_runs.py``: ``kernels.moe_experts`` in the traffic
mix, inside the decode program's runs only). The count is of the work,
whatever implements it. Reads the records' ``kimi_linear`` sizes and the
ticks' ``experts_hit`` / ``expert_rows``; a run whose records carry none
(another runner's), or a program whose ticks carry no such counts, gives
nothing."""

from bench_cells import counts_kimi_linear, decode_runs


def read(run):
    found = decode_runs.traced_decode_ticks(
        run, "kimi_linear", ("experts_hit", "expert_rows"))
    if found is None:
        return None
    cfg, ticks = found
    return decode_runs.roofline_pct(run, "moe_experts", [
        counts_kimi_linear.held_experts_bytes(cfg, t["experts_hit"],
                                              t["expert_rows"])
        for t in ticks])
