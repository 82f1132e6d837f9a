"""Share of the traced stretch in which no operation ran on the device,
mean over the cell's devices (training cells)."""

from bench_cells.reduce import xplane


def read(run):
    trace = run["trace"]
    if run["records"].get("kind") != "train" or trace is None:
        return None
    return 100.0 * xplane.idle_share(trace)
