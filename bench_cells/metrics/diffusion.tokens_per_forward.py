"""Tokens a block forward yields: over the window's ``engine.tick`` spans,
``emitted`` (tokens requests received: all of them come from commits, a
prefill yields none) over ``forwards`` (slots that ran a forward of their
block, denoising or committing). A block of ``B`` positions at ``steps``
denoising forwards costs ``steps + 1`` forwards, so the static schedule's
ceiling is ``B / (steps + 1)`` (0.8 at 4 and 4); answers cut inside their
last block and blocks that a prompt's remainder opens lie under it. A
program whose ticks count no forwards gives nothing."""

from bench_cells import program_spans


def read(run):
    w = program_spans.serve_window(run)
    if w is None or any("forwards" not in t.attrs for t in w.ticks):
        return None
    forwards = sum(t.attrs["forwards"] for t in w.ticks)
    if not forwards:
        return None
    return sum(t.attrs["emitted"] for t in w.ticks) / forwards
