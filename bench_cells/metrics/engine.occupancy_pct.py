"""Decoding slots per tick over ``n_slots``, mean over the window's ticks
(a count: tokens the batched decode emitted in the tick)."""

from bench_cells import readings


def read(run):
    r = run["records"]
    if r.get("kind") != "serve" or not r["ticks"]:
        return None
    decoded = readings.decode_tokens_by_tick(r)
    return 100.0 * sum(decoded.values()) / (len(r["ticks"]) * r["n_slots"])
