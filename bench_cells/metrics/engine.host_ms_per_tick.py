"""Median over the window's ticks of the program's ``engine.tick`` span less
its ``*.wait`` children: what the host does while, in this synchronous loop,
the device has nothing queued. Also prints the share of tick time that lies
in no child span (the spans are meant to cover the tick)."""

import sys

from bench_cells import program_spans, readings


def read(run):
    w = program_spans.serve_window(run)
    if w is None:
        return None
    waits = w.per_tick(("engine.prefill.wait", "engine.decode.wait"))
    host = [program_spans.seconds(t) - x for t, x in zip(w.ticks, waits)]
    print(f"program spans: {len(w.ticks)} ticks, "
          f"{100.0 * w.uncovered_share():.2f} % of tick time in no child "
          f"span", file=sys.stderr, flush=True)
    return 1e3 * readings.percentile(host, 50)
