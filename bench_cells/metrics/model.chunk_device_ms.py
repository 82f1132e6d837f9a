"""Device-busy time inside one run of the prefill chunk's program (device
trace), the median over the traced runs: in a cell whose prompts are many
chunks long half the ticks carry one, and it sets their length
(``model.decode_device_ms`` reads the decode program alone). Reads the
records' ``cohere2`` sizes; a run whose records carry none (another
runner's), an untraced one, or a trace without a chunk run gives nothing."""

import statistics

from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    if r.get("kind") != "serve" or trace is None or r.get("cohere2") is None:
        return None
    dev = trace.devices[0]
    runs = xplane.module_runs(dev, run["mix"]["programs"]["prefill_chunk"])
    if not runs:
        return None
    return 1e3 * statistics.median(
        xplane.total(xplane.merge(
            (e.start, e.end) for e in xplane.ops_within(dev, [m])))
        for m in runs)
