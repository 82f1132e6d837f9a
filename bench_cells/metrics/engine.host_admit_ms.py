"""Mean a tick of the program's ``engine.admit`` span: transfers' progress,
the gate probe, boarding."""

from bench_cells import program_spans


def read(run):
    return program_spans.mean_ms_per_tick(run, ("engine.admit",))
