"""The comparison that decides ``correct``: each number beside a limit of
its own (the limits live in the traffic mix's file, under ``check.limits``,
and ``PERF.md`` gives the readings each was set from)."""

from __future__ import annotations

import statistics


def leaf_gaps(program: list[float], reference: list[float]) -> list[float]:
    """For every leaf, the gap between the program's norm and the
    reference's (not the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger: some
    gradients are all but zero."""
    if len(program) != len(reference):
        raise ValueError(f"{len(program)} program leaves against "
                         f"{len(reference)} reference leaves")
    floor = statistics.median(reference)
    return [abs(p - r) / max(r, floor) for p, r in zip(program, reference)]


def compare(values: dict, limits: dict) -> tuple[bool, dict]:
    """``values`` against ``limits`` (every value needs a limit; a missing
    one is an error, not a pass). Returns ``(correct, {name: {"value",
    "limit"}})``; a value that is not a finite number is not correct."""
    out, ok = {}, True
    for name, value in values.items():
        if name not in limits:
            raise SystemExit(f"bench_cells: no limit for {name!r} in the "
                             f"traffic mix's check.limits")
        limit = limits[name]
        out[name] = {"value": value, "limit": limit}
        if not (value == value and abs(value) != float("inf")
                and value <= limit):
            ok = False
    return ok, out
