"""The share of a decode run's device time that is the delta-rule
recurrence over the slots' matrix states: the summed device time of the
``kda_recurrence`` kernel's events inside the decode program's runs over
those runs' device-busy time (``bench_cells/decode_runs.py``; the
denominator is ``model.decode_device_ms``'s). With the paged-attention
events' share beside it (printed on stderr) it says how much of a tick the
two mechanisms this family brings take. Reads the records' ``kimi_linear``
sizes; a run whose records carry none (another runner's), or an untraced
one, gives nothing."""

import re
import sys

from bench_cells import decode_runs
from bench_cells.reduce import xplane


def read(run):
    r, trace = run["records"], run["trace"]
    if (r.get("kind") != "serve" or trace is None
            or r.get("kimi_linear") is None):
        return None
    _, ops, events = decode_runs.kernel_events(run, "kda_recurrence")
    busy = xplane.total(xplane.merge((e.start, e.end) for e in ops))
    share = lambda es: 100.0 * sum(e.seconds for e in es) / busy  # noqa: E731
    others = {k: re.compile(run["mix"]["kernels"][k])
              for k in ("paged_attention", "moe_experts")}
    print(f"decode runs' device time: kda_recurrence {share(events):.2f} %, "
          + ", ".join(f"{k} {share([e for e in ops if rx.search(e.text)]):.2f}"
                      f" %" for k, rx in others.items()),
          file=sys.stderr, flush=True)
    return share(events)
