"""The pipeline's measured bubble: inside each traced step, from the first
to the last device operation of the step's program on a device, the share
in which no operation ran; the mean over the steps of the worst stage
device. (The schedule's (S-1)/(M+S-1) is printed beside it, never in its
place.)"""

from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    if (r.get("kind") != "train" or trace is None or r["n_stages"] < 2
            or not r["traced_steps"]):
        return None
    pattern = run["mix"]["programs"]["train_step"]
    worst = None
    for dev in trace.devices:
        shares = []
        for m in xplane.module_runs(dev, pattern):
            ops = xplane.ops_within(dev, [m])
            if not ops:
                continue
            lo, hi = min(e.start for e in ops), max(e.end for e in ops)
            busy = xplane.total(xplane.merge((e.start, e.end) for e in ops))
            shares.append(1.0 - busy / (hi - lo))
        if shares:
            mean = sum(shares) / len(shares)
            worst = mean if worst is None else max(worst, mean)
    if worst is None:
        raise SystemExit(f"bench_cells: no run of a program matching "
                         f"{pattern!r} in the trace")
    return 100.0 * worst
