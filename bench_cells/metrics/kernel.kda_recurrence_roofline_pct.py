"""The delta-rule recurrence kernel's memory-bound roofline share inside the
decode tick's program: the bytes its calls have to move
(``bench_cells/counts_kimi_linear.py::kda_bytes``: per KDA layer each LIVE
slot's ``[heads, dk, dk]`` float32 state in and out, ``q``, ``k``, ``g``,
``v`` and ``beta`` in, ``o`` out) over the chip's HBM bandwidth, divided by
the summed device time of the kernel's events inside the decode program's
runs (``bench_cells/decode_runs.py``: ``kernels.kda_recurrence`` in the
traffic mix; a prefill chunk's walks are left out on both sides). The live
slots are the program's own count, ``decoding`` on every ``engine.tick``
span; a slot that sits a tick out has its block copied through all the same
and is not counted, so a tick of few live slots reads LOW. Reads the
records' ``kimi_linear`` sizes; a run whose records carry none (another
runner's), or an untraced one, gives nothing."""

from bench_cells import counts_kimi_linear, decode_runs


def read(run):
    found = decode_runs.traced_decode_ticks(run, "kimi_linear",
                                            ("decoding",))
    if found is None:
        return None
    cfg, ticks = found
    return decode_runs.roofline_pct(run, "kda_recurrence", [
        counts_kimi_linear.kda_bytes(cfg, t["decoding"]) for t in ticks])
