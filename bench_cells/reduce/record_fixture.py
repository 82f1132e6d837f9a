"""Record the small trace the reduction's tests run on: ``python -m
bench_cells.reduce.record_fixture <out.xplane.pb>`` on a machine with a TPU.
Three steps of a small program (two matmuls and a reduction) under the
harness's spans, with a host sleep between the steps so that the trace has
idle gaps to attribute."""

from __future__ import annotations

import shutil
import sys
import time

import jax
import jax.numpy as jnp

from bench_cells import harness


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture: needs a TPU")

    @jax.jit
    def fixture_step(x, w):
        h = jnp.tanh(x @ w)
        return (h @ w.T).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16)
    fixture_step(x, w).block_until_ready()
    spans = harness.Spans()
    tracer = harness.Tracer(True)
    tracer.start()
    for _ in range(3):
        with spans.span("bench.fixture.step"):
            with spans.span("bench.fixture.dispatch"):
                y = fixture_step(x, w)
            with spans.span("bench.fixture.block"):
                y.block_until_ready()
        with spans.span("bench.fixture.sleep"):
            time.sleep(0.002)
    tracer.stop()
    shutil.copy(tracer.xplane_path(), out)
    tracer.cleanup()


if __name__ == "__main__":
    main(sys.argv[1])
