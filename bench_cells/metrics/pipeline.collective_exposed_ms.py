"""Per step, the time in which a collective (``ppermute`` hop, all-reduce)
ran on a device and no other operation did; the worst device."""

from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    if (r.get("kind") != "train" or trace is None or r["n_stages"] < 2
            or not r["traced_steps"]):
        return None
    worst = max(xplane.exposed_collective_seconds(d) for d in trace.devices)
    return 1e3 * worst / r["traced_steps"]
