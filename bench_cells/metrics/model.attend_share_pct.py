"""The share of a decode run's device time that is attention over the cache:
the summed device time of the paged-attention kernel's events inside the
decode program's runs over those runs' device-busy time
(``bench_cells/decode_runs.py``; the denominator is
``model.decode_device_ms``'s). Where the cache is long and narrow this is
what the latent buys or costs; in a cell of short contexts it is small
whatever the kernel does. Reads the records' ``zaya`` sizes; a run whose
records carry none (another runner's), or an untraced one, gives nothing."""

from bench_cells import decode_runs
from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    if r.get("kind") != "serve" or trace is None or r.get("zaya") is None:
        return None
    _, ops, events = decode_runs.kernel_events(run, "paged_attention")
    busy = xplane.total(xplane.merge((e.start, e.end) for e in ops))
    return 100.0 * sum(e.seconds for e in events) / busy
