"""What the device-trace readers of a decode tick's kernels share: the traced
ticks that decoded (their ``engine.tick`` spans carry the program's counts)
and the decode program's runs in the trace with the operations inside them.

The engine launches a tick's decode in the tick before, so the runs the
trace holds are those ticks' shifted by one: a mean over the ticks is the
same mean over the runs, but for a run cut at either end. A prefill chunk's
calls of the same kernels are left out on both sides: its ticks carry no
count.
"""

from __future__ import annotations

import re
import statistics

from bench_cells import program_spans
from bench_cells.reduce import xplane


def traced_decode_ticks(run, family: str, needs: tuple):
    """``(the family's sizes, the attrs of the traced ticks that decoded)``,
    or ``None`` where there is nothing to read: an untraced run, another
    kind of cell, records that carry no ``family`` sizes (another runner's),
    a program without the recorder, or ticks that lack one of ``needs``
    (a program older than the count)."""
    r, trace = run["records"], run["trace"]
    cfg = r.get(family)
    if r.get("kind") != "serve" or trace is None or cfg is None:
        return None
    w = program_spans.serve_window(run)
    if w is None:
        return None
    first, last = r["traced_ticks"]
    ticks = [t.attrs for t in program_spans.window_ticks(r, w.spans)[
        first:last] if t is not None and t.attrs.get("decoding")]
    if not ticks or any(k not in t for t in ticks for k in needs):
        return None
    return cfg, ticks


def decode_runs(run):
    """Device 0's runs of the decode program; none is an error."""
    dev = run["trace"].devices[0]
    pattern = run["mix"]["programs"]["decode_tick"]
    runs = xplane.module_runs(dev, pattern)
    if not runs:
        raise SystemExit(f"bench_cells: no run of a program matching "
                         f"{pattern!r} in the trace")
    return dev, runs


def kernel_events(run, kernel: str):
    """``(the decode program's runs, every device operation inside them,
    those of them that the mix calls ``kernel``)``; finding none of the
    last is an error, not a zero."""
    dev, runs = decode_runs(run)
    pattern = run["mix"]["kernels"][kernel]
    rx = re.compile(pattern)
    ops = xplane.ops_within(dev, runs)
    events = [e for e in ops if rx.search(e.text)]
    if not events:
        raise SystemExit(f"bench_cells: no device operation matching "
                         f"{pattern!r} inside the decode program's runs")
    return runs, ops, events


def roofline_pct(run, kernel: str, bytes_of_each_tick) -> float:
    """The memory-bound roofline share of ``kernel`` inside the decode
    runs: the mean of a run's bytes times the runs, over the chip's HBM
    bandwidth, over the summed device time of the kernel's events."""
    runs, _, events = kernel_events(run, kernel)
    return (100.0 * statistics.fmean(bytes_of_each_tick) * len(runs)
            / run["peaks"]["hbm_bytes_per_s"]
            / sum(e.seconds for e in events))
