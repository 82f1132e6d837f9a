"""Look at a trace by hand: ``python -m bench_cells.reduce.describe
<file.xplane.pb>`` prints its planes and lines, the first events of each
with their stats, and the operations that took most time."""

from __future__ import annotations

import sys

from bench_cells.reduce import xplane


def describe(path: str, out=sys.stdout, first: int = 3, top: int = 25):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name}", file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            for ev in events[:first]:
                print(f"      {ev.name!r} start {ev.start_ns:.0f} ns, "
                      f"{ev.duration_ns:.0f} ns, stats "
                      f"{dict(ev.stats)}", file=out)
            acc: dict[str, list] = {}
            for ev in events:
                a = acc.setdefault(ev.name, [0, 0.0])
                a[0] += 1
                a[1] += ev.duration_ns
            ranked = sorted(acc.items(), key=lambda kv: -kv[1][1])[:top]
            for name, (n, ns) in ranked:
                print(f"      top {name[:300]!r}: {n} x, {ns / 1e6:.3f} ms",
                      file=out)
            kinds: dict[str, list] = {}
            for name, (n, ns) in acc.items():
                kind = xplane.op_kind(name) or name.split("(")[0][:40]
                k = kinds.setdefault(kind, [0, 0.0, name])
                k[0] += n
                k[1] += ns
            for kind, (n, ns, sample) in sorted(
                    kinds.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"      kind {kind!r}: {n} x, {ns / 1e6:.3f} ms, e.g. "
                      f"{sample[:400]!r}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
