"""Per request of the window: the start of its first
``engine.prefill.prepare`` less the start of its ``engine.submit``, the
median: the part of ``engine.ttft_p50_ms`` that is waiting, for a slot and
then for the chunk turn."""

from bench_cells import program_spans, readings


def read(run):
    w = program_spans.serve_window(run)
    if w is None:
        return None
    submitted, first_chunk = {}, {}
    for s in w.spans:
        if s.name == "engine.submit":
            submitted[s.attrs["rid"]] = s.start_ns
        elif s.name == "engine.prefill.prepare":
            rid = s.attrs["rid"]
            first_chunk[rid] = min(first_chunk.get(rid, s.start_ns),
                                   s.start_ns)
    waits = [first_chunk[rid] - t for rid, t in submitted.items()
             if rid in first_chunk]
    if not waits:
        raise SystemExit("bench_cells: no request of the window has both "
                         "an engine.submit and an engine.prefill.prepare "
                         "span")
    return 1e-6 * readings.percentile(waits, 50)
