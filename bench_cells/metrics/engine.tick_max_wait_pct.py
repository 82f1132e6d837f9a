"""The share of the window's longest ``engine.tick`` that lies inside its
``*.wait`` children, by the spans' own stamps: a decode-and-chunk tick
waits for the device through most of itself; a stalled tick that reads near
100 stood in a wait (the runtime's or the chip's, by its ``ready``), one
that reads low held the stall in the host's own stretch (a collection, a
call that did not return). Read over the whole window, traced or not: the
host's half needs no profiler. The tick that follows the traced stretch is
left out (``program_runs.longest_tick``). The stall record is one line on
stderr (``program_runs.stall_record``)."""

import sys

from bench_cells import program_runs


def read(run):
    w, runs = program_runs.window_runs(run)
    if runs is None:
        return None
    longest, after = program_runs.longest_tick(run, w)
    print(program_runs.stall_record(w, program_runs.join(run), longest,
                                    after), file=sys.stderr, flush=True)
    return 100.0 * program_runs.wait_share(longest, w)
