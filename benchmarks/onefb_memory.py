"""Compiled-memory comparison of the two pipeline schedules.

The 1F1B schedule's reason to exist is that activation memory stays flat in
the microbatch count M while GPipe's grows linearly (its autodiff keeps all
M microbatches' residuals alive between the forward and backward sweeps).
This harness records XLA's own memory analysis (temp allocation bytes of
the compiled loss+grads program) for both schedules over a sweep of M —
hardware-independent evidence (the analysis is of the compiled program, not
a runtime measurement), runnable on the virtual-CPU mesh.

Prints one JSON line per (schedule, M) and writes
``benchmarks/onefb_memory.json``.
"""

from __future__ import annotations

import json
import os

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "onefb_memory.json")


def temp_bytes(schedule: str, m: int) -> int:
    """Temp allocation of the compiled loss+grads program for one schedule
    at ``m`` microbatches. The SAME helper backs both this benchmark and
    tests/test_onefb.py's memory-scaling assertion, so the recorded
    artifact and the CI guarantee can never measure different programs.
    Requires an initialized jax (any backend; the test and main() both use
    the 8-virtual-device CPU mesh)."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    from simple_distributed_machine_learning_tpu.models.mlp import (
        make_mlp_stages,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )

    stages, wire, out = make_mlp_stages(jax.random.key(0), [256, 256, 10], 2)
    mesh = make_mesh(n_stages=2, n_data=1)
    p = Pipeline(stages, mesh, wire, out, n_microbatches=m,
                 schedule=schedule)
    x = jax.random.normal(jax.random.key(1), (16 * m, 256))
    y = jax.random.randint(jax.random.key(2), (16 * m,), 0, 10)
    buf = p.init_params()
    f = jax.jit(lambda b: p.loss_and_grads(b, x, y, jax.random.key(3),
                                           deterministic=True))
    return int(f.lower(buf).compile().memory_analysis().temp_size_in_bytes)


def main() -> None:
    # compile-only memory analysis on 8 virtual CPU devices
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax

    rows = []
    for m in (1, 4, 16, 64):
        for sched in ("gpipe", "1f1b"):
            row = {"schedule": sched, "microbatches": m,
                   "temp_bytes": temp_bytes(sched, m)}
            rows.append(row)
            print(json.dumps(row))
    with open(OUT, "w") as f:
        json.dump(rows, f, indent=2)


if __name__ == "__main__":
    main()
