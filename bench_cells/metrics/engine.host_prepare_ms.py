"""Mean a tick of ``engine.prefill.prepare`` + ``engine.decode.prepare``:
block allocation, the tables, the sampling inputs."""

from bench_cells import program_spans


def read(run):
    return program_spans.mean_ms_per_tick(
        run, ("engine.prefill.prepare", "engine.decode.prepare"))
