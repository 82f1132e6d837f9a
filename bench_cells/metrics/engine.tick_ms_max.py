"""The longest ``engine.tick`` of the window; what lay inside it (a
``jax.compile``, a ``py.gc`` among the phases) is printed on stderr."""

import sys

from bench_cells import program_spans


def read(run):
    w = program_spans.serve_window(run)
    if w is None:
        return None
    longest = max(w.ticks, key=program_spans.seconds)
    inside = ", ".join(
        f"{s.name} {1e3 * program_spans.seconds(s):.2f}"
        for s in sorted(program_spans.descendants(longest, w.kids),
                        key=lambda s: s.start_ns))
    print(f"longest tick: {1e3 * program_spans.seconds(longest):.2f} ms "
          f"{longest.attrs}; inside it (ms): {inside}", file=sys.stderr,
          flush=True)
    return 1e3 * program_spans.seconds(longest)
