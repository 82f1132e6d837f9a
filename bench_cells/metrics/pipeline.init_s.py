"""The program's ``pipeline.init`` span: ``Pipeline.__init__``'s eager pack
on the first device, the row's trip to the host and the boundary check (its
children ``pipeline.pack`` and ``pipeline.to_host`` are printed)."""

import sys

from bench_cells import program_spans


def read(run):
    if run["records"].get("kind") != "train":
        return None
    tracer = program_spans.recorder()
    if tracer is None:
        return None
    spans = tracer.spans()
    inits = [s for s in spans if s.name == "pipeline.init"]
    if len(inits) != 1:
        raise SystemExit(f"bench_cells: {len(inits)} pipeline.init spans in "
                         f"the program's recorder; expected one")
    parts = [s for s in program_spans.children_of(spans).get(inits[0].id, ())
             if s.name.startswith("pipeline.")]
    print("pipeline.init: " + ", ".join(
        f"{s.name} {program_spans.seconds(s):.3f} s {s.attrs}"
        for s in parts), file=sys.stderr, flush=True)
    return program_spans.seconds(inits[0])
