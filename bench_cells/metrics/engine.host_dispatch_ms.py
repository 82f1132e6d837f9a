"""Mean a tick of the two ``*.dispatch`` spans (argument transfer and
enqueue), less any ``jax.*`` child: a trace, lowering or compile inside a
dispatch is a stall of its own (``engine.tick_ms_max`` names it)."""

from bench_cells import program_spans


def read(run):
    return program_spans.mean_ms_per_tick(
        run, ("engine.prefill.dispatch", "engine.decode.dispatch"),
        less=("jax.",))
