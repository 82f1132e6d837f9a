"""Of the device's idle time inside the traced ticks, the share that falls
inside a span of the program below ``engine.tick`` once both are on one
clock. Idle inside a ``*.wait`` span is read-back latency, not host work;
the idle seconds by (innermost) span name are printed on stderr."""

import sys

from bench_cells import program_spans


def read(run):
    r, trace = run["records"], run["trace"]
    if r.get("kind") != "serve" or trace is None:
        return None
    tracer = program_spans.recorder()
    if tracer is None:
        return None
    idle = program_spans.idle_by_span(r, trace, tracer)
    whole = sum(idle.values())
    first, last = r["traced_ticks"]
    print(f"device idle inside the {last - first} traced ticks: "
          f"{whole:.4f} s; by program span: "
          + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
              idle.items(), key=lambda kv: -kv[1])),
          file=sys.stderr, flush=True)
    if whole <= 0:
        return None
    return 100.0 * (whole - idle.get(program_spans.NO_SPAN, 0.0)) / whole
