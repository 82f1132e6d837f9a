"""The grouped selective-scan kernel's memory-bound roofline share: the
bytes its calls inside the traced ticks have to move
(``bench_cells/counts_nemotron_h.py``: state in and out, ``x`` in, ``y``
out, one ``delta`` a head, ``B`` and ``C`` a group) over the chip's HBM
bandwidth, divided by the summed device time of the kernel's events. One
call per Mamba layer in every program run: a decode tick's call walks one
token of every slot, a prefill chunk's the chunk's tokens of one slot (``n``
on the program's ``engine.prefill.prepare`` span). The kernel is found by
what the trace calls it (``kernels.selective_scan_grouped`` in the traffic
mix); finding no event is an error, not a zero. The engine launches a
tick's decode in the tick before, so the decode runs the trace holds are
those of the traced ticks shifted by one: the same number, but for a run
cut at either end. A run whose records carry no ``nemotron_h`` sizes
(another runner's) gives nothing."""

import re

from bench_cells import counts_nemotron_h, program_spans


def read(run):
    r, trace = run["records"], run["trace"]
    cfg = r.get("nemotron_h")       # the runner's: the model's own sizes
    if r.get("kind") != "serve" or trace is None or cfg is None:
        return None
    w = program_spans.serve_window(run)
    if w is None:
        return None
    pattern = run["mix"]["kernels"]["selective_scan_grouped"]
    rx = re.compile(pattern)
    events = [e for e in trace.devices[0].ops if rx.search(e.text)]
    if not events:
        raise SystemExit(f"bench_cells: no device operation matching "
                         f"{pattern!r} in the trace: the grouped "
                         f"selective-scan kernel was not found")
    first, last = r["traced_ticks"]
    least = 0
    for tick in program_spans.window_ticks(r, w.spans)[first:last]:
        if tick is None:
            continue
        if tick.attrs["decoding"]:
            least += counts_nemotron_h.mamba2_scan_bytes(
                cfg, r["n_slots"], 1)
        for c in w.kids.get(tick.id, ()):
            if c.name == "engine.prefill.prepare":
                least += counts_nemotron_h.mamba2_scan_bytes(
                    cfg, 1, c.attrs["n"])
    seconds = sum(e.seconds for e in events)
    return (100.0 * cfg["pattern"].count("M") * least
            / run["peaks"]["hbm_bytes_per_s"] / seconds)
