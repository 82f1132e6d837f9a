"""The latent expert products' memory-bound roofline share inside the decode
tick's program: the bytes they have to move
(``bench_cells/counts_nemotron_h.py``: each hit held expert's two matrices
once a run, the routed rows in and their results out at the latent's width)
over the chip's HBM bandwidth, divided by the summed device time of the
operations that do them. The count is of the work, whatever implements it;
the operations are found by what the trace calls them
(``kernels.moe_experts`` in the traffic mix), inside the runs of the decode
program only (a prefill chunk's calls are left out on both sides: its ticks
carry no count). ``experts_hit`` and ``expert_rows`` are summed over the
expert layers on every ``engine.tick`` span, and the byte count is linear in
both, so one call with the sums gives a run's bytes; a run moves the mean of
the traced ticks that decoded (the engine launches a tick's decode in the
tick before, so the runs the trace holds are those ticks' shifted by one:
the same mean, but for a run cut at either end). Finding no operation is an
error, not a zero. A run whose records carry no ``nemotron_h`` sizes
(another runner's), or a program whose ticks carry no such counts, gives
nothing."""

import re
import statistics

from bench_cells import counts_nemotron_h, program_spans
from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    cfg = r.get("nemotron_h")       # the runner's: the model's own sizes
    if r.get("kind") != "serve" or trace is None or cfg is None:
        return None
    w = program_spans.serve_window(run)
    if w is None:
        return None
    first, last = r["traced_ticks"]
    ticks = [t for t in program_spans.window_ticks(r, w.spans)[first:last]
             if t is not None and t.attrs.get("decoding")]
    if not ticks or any("expert_rows" not in t.attrs for t in ticks):
        return None
    dev = trace.devices[0]
    runs = xplane.module_runs(dev, run["mix"]["programs"]["decode_tick"])
    pattern = run["mix"]["kernels"]["moe_experts"]
    rx = re.compile(pattern)
    events = [e for e in xplane.ops_within(dev, runs) if rx.search(e.text)]
    if not events:
        raise SystemExit(f"bench_cells: no device operation matching "
                         f"{pattern!r} inside the decode program's runs: "
                         f"the expert products were not found")
    a_run = statistics.fmean(
        counts_nemotron_h.latent_experts_bytes(
            cfg, t.attrs["experts_hit"], t.attrs["expert_rows"])
        for t in ticks)
    seconds = sum(e.seconds for e in events)
    return (100.0 * a_run * len(runs) / run["peaks"]["hbm_bytes_per_s"]
            / seconds)
