"""Per traced tick, device 0's idle between the end of one program run and
the start of the next that lies BEFORE the next run's dispatch span began:
the device had nothing asked of it. With its sibling and the idle inside
runs it adds up to the traced ticks' device idle; the three, the count of
runs paired and the join's error are printed on stderr."""

from bench_cells import program_runs


def read(run):
    return program_runs.read_device_wait(run, "host")
