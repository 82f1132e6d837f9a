"""One cell, once: ``python -m bench_cells.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

One process, no child. Needs the cell's TPU chips (never a CPU run), builds
the weights on the device from the seed, warms the shapes the window uses,
measures for ``--seconds``, compares what the timed path produced with the
plain reference, and prints one JSON object as the last line of standard
output. Anything that goes wrong ends the run with a non-zero code and no
result line.
"""

from __future__ import annotations

import time

_T_START = time.time()          # before the heavy imports: set-up counts them

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402


from bench_cells import check, harness, manifest  # noqa: E402
from bench_cells.reduce import xplane  # noqa: E402


def _say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             peaks: dict, keep_trace: str | None = None,
             control: bool = False) -> dict:
    """Everything after the look for a chip: set-up, window, check, result."""
    compiles = harness.Compiles()
    spans = harness.Spans()
    runner = importlib.import_module(
        f"bench_cells.runners.{cell.traffic['runner']}")
    run = runner.Run(cell, seed, spans)
    t_import = time.time() - _T_START
    split = run.setup()
    setup = {"import_s": t_import, **split, **compiles.seconds}
    setup["setup_s"] = time.time() - _T_START
    _say("set-up:", json.dumps(setup))

    events_before = compiles.events
    tracer = harness.Tracer(trace)
    try:
        run.window(seconds, tracer)
        compiled_in_window = compiles.events - events_before
        peak = harness.memory_peak_bytes()
        reduced = None
        if trace:
            path = tracer.xplane_path()
            if path is None:
                raise SystemExit("bench_cells: the profiler wrote no trace")
            if keep_trace:
                shutil.copy(path, keep_trace)
            reduced = xplane.load(path)
    finally:
        tracer.cleanup()

    records = run.records
    ctx = {"records": records, "setup": setup, "trace": reduced,
           "peaks": peaks, "chips": cell.chips,
           "gpt": cell.config.get("gpt_config"), "mix": cell.traffic}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if reduced is not None:
        lo, hi = reduced.bounds
        device["busy_s"] = xplane.busy_seconds(reduced)
        device["window_s"] = hi - lo
        breakdown = {"device_ops": xplane.top_ops(reduced),
                     "idle_gaps": xplane.idle_by_span(reduced)}

    # the comparison comes last: the program's state is freed first and the
    # reference must not set the memory peak
    values = run.check()
    values["compiles_in_window"] = compiled_in_window
    correct, compared = check.compare(values, cell.traffic["check"]["limits"])
    _say("check:", json.dumps(records.get("check_detail", {})),
         f"reference {records.get('reference_s', 0.0):.1f} s")
    if control:
        ok, ctl = check.compare(run.control(),
                                cell.traffic["check"]["limits"])
        _say("control:", json.dumps({"correct": ok, "compared": ctl,
                                     "detail": records.get("check_detail")}))
    result = {"correct": correct, "attempted": records["attempted"],
              "failed": records["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["setup_split"] = setup
    result["compared"] = compared
    for name, c in compared.items():
        _say(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the run's .xplane.pb here (a builder's aid; "
                         "the driver never passes it)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also print the control's numbers on stderr (a "
                         "builder's aid; the driver never passes it)")
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    device = harness.require_tpu(cell.chips)
    peaks = manifest.load_peaks(device["kind"])
    from simple_distributed_machine_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, peaks, args.keep_trace, bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
