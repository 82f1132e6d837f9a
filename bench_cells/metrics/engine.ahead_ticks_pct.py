"""How often the engine's next decode was already on the device: of the
window's ticks that decoded (``decoding`` > 0 on their ``engine.tick``
span), the share whose decode the tick before had dispatched (``ahead``,
1 or 0). Traffic with ``eos_id`` set, or a storm of preemptions, turns the
dispatch off without any other sign. A program whose ticks carry no such
attribute gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    w = program_spans.serve_window(run)
    if w is None or any("ahead" not in t.attrs for t in w.ticks):
        return None
    decoded = [t.attrs["ahead"] for t in w.ticks if t.attrs["decoding"]]
    return 100.0 * statistics.fmean(decoded) if decoded else None
