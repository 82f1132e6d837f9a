"""The paged-attention kernel's memory-bound roofline share: the live K/V
bytes the traced ticks' positions make it read (``bench_cells/flops.py``)
over the chip's HBM bandwidth, divided by the summed device time of the
kernel's events. The kernel is found by what the trace calls it; finding
no event is an error, not a zero."""

import re

from bench_cells import flops, readings


def read(run):
    r, trace = run["records"], run["trace"]
    if r.get("kind") != "serve" or trace is None:
        return None
    pattern = run["mix"]["kernels"]["paged_attention"]
    rx = re.compile(pattern)
    events = [e for e in trace.devices[0].ops if rx.search(e.text)]
    if not events:
        raise SystemExit(f"bench_cells: no device operation matching "
                         f"{pattern!r} in the trace: the paged-attention "
                         f"kernel was not found")
    first, last = r["traced_ticks"]
    live = sum(n for tick, n in readings.live_positions_by_tick(r).items()
               if first <= tick < last)
    least = (flops.paged_attention_bytes(run["gpt"], live,
                                         r["cache_itemsize"])
             / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / sum(e.seconds for e in events)
