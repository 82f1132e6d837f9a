"""Per traced tick, device 0's idle between the end of one program run and
the start of the next that lies AFTER the next run's dispatch span began
and before its first operation: the call, the runtime and the chip."""

from bench_cells import program_runs


def read(run):
    return program_runs.read_device_wait(run, "launch")
