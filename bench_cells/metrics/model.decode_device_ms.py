"""Device-busy time inside one run of the decode tick's program (device
trace), mean over the traced ticks."""

from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    if r.get("kind") != "serve" or trace is None:
        return None
    dev = trace.devices[0]
    pattern = run["mix"]["programs"]["decode_tick"]
    runs = xplane.module_runs(dev, pattern)
    if not runs:
        raise SystemExit(f"bench_cells: no run of a program matching "
                         f"{pattern!r} in the trace")
    ops = xplane.ops_within(dev, runs)
    busy = xplane.total(xplane.merge((e.start, e.end) for e in ops))
    return 1e3 * busy / len(runs)
