"""Median over the traced runs that were waited for of the wait's end less
the later of the wait's start and the run's end on the device: how long the
tokens take from the device to the host thread once both are ready for
them. The largest, with its run, tick and ``ready``, is printed on stderr.
The reading carries in full however far the device's stamps lie before the
host's clock in this trace (1.0-1.2 ms, 2.0 in a machine's first traced
process: ``PERF.md``, Open question 15); the ``host`` reader's stderr line
says how far."""

import sys

from bench_cells import program_runs, program_spans, readings


def read(run):
    j = program_runs.join(run)
    if j is None:
        return None
    back = program_runs.readbacks(j)
    if not back:
        raise SystemExit("bench_cells: no traced program run has a wait "
                         "span that read it")
    worst, r = max(back, key=lambda x: x[0])
    w, _ = program_runs.window_runs(run)
    tick = program_runs.tick_of(r.wait, w)
    print(f"read-back: {len(back)} traced runs waited for, the largest "
          f"{1e3 * worst:.3f} ms: {r.program} run {r.run} in tick "
          f"{tick.attrs.get('tick') if tick else None}, ready "
          f"{r.wait.attrs.get('ready')}, the wait "
          f"{1e3 * program_spans.seconds(r.wait):.3f} ms",
          file=sys.stderr, flush=True)
    return 1e3 * readings.percentile([x for x, _ in back], 50)
