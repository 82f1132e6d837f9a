"""The share of a window layer's cache that lay behind the window and was
handed back: over the window's ``engine.tick`` spans that decoded, the mean
of ``1 - kv_window_blocks / kv_full_blocks`` (the blocks ONE window layer
and ONE full layer hold at the tick's end, ``serve/slots.py``'s groups). 0
would be a pool with one layer kind (every layer holds every position); a
slot inside its first window holds the same in both, a slot five windows
deep a fifth. Reads the records' ``cohere2`` sizes; a run whose records
carry none (another runner's), or a program whose ticks carry no such
counts (one older than the pool's groups), gives nothing."""

import statistics

from bench_cells import program_spans


def read(run):
    cfg = run["records"].get("cohere2")
    w = program_spans.serve_window(run)
    if cfg is None or w is None or any(
            "kv_window_blocks" not in t.attrs or "kv_full_blocks"
            not in t.attrs for t in w.ticks):
        return None
    shares = [1.0 - t.attrs["kv_window_blocks"] / t.attrs["kv_full_blocks"]
              for t in w.ticks
              if t.attrs["decoding"] and t.attrs["kv_full_blocks"]]
    return 100.0 * statistics.fmean(shares) if shares else None
