"""Profiling hooks (SURVEY §5.1: absent in the reference, cheap under JAX).

Wraps ``jax.profiler`` so any training window can be captured as an XProf /
TensorBoard trace — the tool for verifying the pipeline actually overlaps
ICI transfer with compute (the ≥10× claim's mechanism, SURVEY §3.3).
"""

from __future__ import annotations

import contextlib
import os
import sys

import jax


@contextlib.contextmanager
def trace(logdir: str = "/tmp/sdml_trace", enabled: bool = True):
    """``with trace('/tmp/tb') as d: step(...)`` → open ``d`` in
    TensorBoard/XProf.

    Yields the logdir (``None`` when no trace is being captured) so tooling
    can hand the path on. Hardened so the profiler can never take a run
    down or leak a started trace:

    - ``enabled=False`` touches nothing (no directory creation) and yields
      ``None``;
    - an uncreatable ``logdir`` degrades to disabled with a stderr note
      instead of raising — a full disk must not kill the training it was
      profiling;
    - stop is idempotent: it runs only if start actually succeeded, and a
      stop failure (e.g. the body already stopped the trace, or the first
      flush never completed before the body raised) is swallowed so the
      body's own exception — the one that matters — propagates.
    """
    if not enabled:
        yield None
        return
    try:
        os.makedirs(logdir, exist_ok=True)
    except OSError as e:
        print(f"profiler: cannot create trace dir {logdir!r} ({e}); "
              f"tracing disabled for this window", file=sys.stderr)
        yield None
        return
    started = False
    try:
        jax.profiler.start_trace(logdir)
        started = True
    except RuntimeError as e:
        # another trace is already running (nested trace() windows): keep
        # the outer capture alive rather than crashing the run
        print(f"profiler: start_trace failed ({e}); continuing untraced",
              file=sys.stderr)
        yield None
        return
    try:
        yield logdir
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                pass  # already stopped / never fully started: nothing leaks


def annotate_scope(name: str):
    """Named region for ops inside a compiled program (host intervals are
    ``telemetry/tracing.py``'s spans: a ``TraceAnnotation`` entered at trace
    time would label the tracing, not the execution).

    ``jax.named_scope`` prefixes the HLO metadata of every op traced under it,
    which XProf surfaces as a grouped region on the device timeline — the
    right tool for showing that e.g. each chunk of a ring collective matmul
    (``parallel/overlap.py``) has its compute overlapped with the next chunk's
    ICI transfer.
    """
    return jax.named_scope(name)
