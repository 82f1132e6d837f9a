"""Share of the window's ticks that ran a prefill chunk, from the program's
own count (``chunk`` on its ``engine.tick`` spans)."""

from bench_cells import program_spans


def read(run):
    w = program_spans.serve_window(run)
    if w is None:
        return None
    return 100.0 * sum(t.attrs["chunk"] for t in w.ticks) / len(w.ticks)
