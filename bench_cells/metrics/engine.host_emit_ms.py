"""Mean a tick of the two ``*.emit`` spans: the per-slot loop with its
callbacks, prefix registration, seating and retirement."""

from bench_cells import program_spans


def read(run):
    return program_spans.mean_ms_per_tick(
        run, ("engine.prefill.emit", "engine.decode.emit"))
