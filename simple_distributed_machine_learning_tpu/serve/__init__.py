"""Continuous-batching inference serving (the north star's traffic layer).

Every decoder below ``serve/`` (``models/gpt.py`` cached, ``models/beam.py``,
``models/pp_decode.py``) is one-shot: one prompt batch in, all tokens out.
Production TPU serving is dominated by *continuous batching* — admitting and
retiring sequences mid-flight inside one compiled step — and by TTFT/TPOT
latency accounting (PAPERS.md: "Fine-Tuning and Serving Gemma on Cloud TPU").
This package is that layer, on top of the existing KV-cache model ops,
checkpoint restore, and the telemetry registry:

- :mod:`.slots` — the KV-cache pool: :class:`PagedKVPool` (block-table
  paged pool with refcounted blocks, prefix sharing via a
  registered-prompt registry, copy-on-write before divergent writes, and
  reservation-backed on-demand allocation — the layout that makes
  concurrency a function of actual tokens resident, not worst-case rows)
  on the invariant-guarded free-list discipline;
- :mod:`.request` — the request object: prompt, per-request sampling params
  (greedy / top-k / top-p with an independent seeded key stream),
  ``max_new_tokens`` / EOS termination, and latency timestamps;
- :mod:`.scheduler` — FCFS continuous-batching scheduler: admits from the
  queue into free slots, retires on EOS or token budget, freeing slots
  immediately so waiting requests board mid-flight;
- :mod:`.engine` — :class:`InferenceEngine`: ``submit() -> handle``,
  ``step()`` (one tick: admit, at most one prefill CHUNK, then ONE batched
  decode program regardless of occupancy — chunked prefill keeps a long
  prompt from freezing in-flight decodes), ``drain()``, streaming
  per-token callbacks;
- :mod:`.simulator` — open-loop traffic simulator: seeded Poisson arrivals
  at a configurable rate driving the engine (``cli.py --serve-sim``);
- :mod:`.metrics` — serving telemetry on the PR-4 ``MetricsRegistry``:
  queue-depth / slot-occupancy gauges, TTFT and per-output-token latency
  histograms, aggregate tokens/sec — JSONL + Prometheus;
- :mod:`.journal` — the append-only, fsync'd request journal (one record
  per submission / emitted token / completion / shed, carrying live PRNG
  key state and the supervisor's monotonic tick), with a
  corruption-tolerant tail like the checkpoint store's ``latest_valid``;
- :mod:`.tracing` — :class:`ServeTrace`: request-scoped tracing — per-rid
  async span timelines (submit, queue wait, prefill chunks, decode/spec
  ticks, preempt/resume, crash re-admission, completion) exported as
  Chrome-trace async events plus a per-request JSONL timeline; spans join
  across restarts because the journal's rid is the trace id, and the
  recorder never reads a clock (engine-supplied stamps only);
- :mod:`.flight` — :class:`FlightRecorder`: a bounded ring of per-tick
  engine snapshots, dumped by the supervisor as post-mortem bundles
  (flight rows + request states + metrics snapshot + journal tail) on
  every restart, ``DrainTimeout`` and shed burst;
- :mod:`.supervisor` — :class:`ServeSupervisor`: the crash-restartable
  serving loop (RUNNING → RECOVERING → RUNNING | DEGRADED) that rebuilds a
  failed engine and re-admits in-flight requests from the journal
  BIT-EXACT through the preempt/resume machinery, enforces per-request
  TTFT/total deadlines at tick boundaries, and applies
  :class:`OverloadPolicy` admission control (per-class token buckets,
  queue-depth backpressure, degraded modes) — ``cli.py --serve-chaos`` /
  ``--serve-deadline-ms``;
- :mod:`.router` — :class:`FleetRouter`: which replica serves a request —
  prefix-cache affinity over the paged pools' registries first,
  least-loaded by queue-depth/occupancy otherwise, round-robin as the
  affinity-blind baseline;
- :mod:`.fleet` — :class:`ServeFleet` + :class:`AutoscalePolicy`: N
  supervised replicas behind the router with fleet-unique rids,
  health-aware rotation (hysteresis re-entry), JOURNAL-BACKED
  cross-replica migration on replica loss (every in-flight stream
  re-admitted onto survivors bit-exact from the dead replica's journal
  alone), and a queue-depth/KV-residency autoscaler (scale-out on
  sustained backlog, drain-then-retire on idle) —
  ``cli.py --serve-replicas``.

Correctness anchor (tests/test_serve.py): with the same seed, every
request's tokens are bit-exact vs decoding it alone through
``models.make_cached_decoder`` — continuous batching is a scheduling
optimization, not a math change.
"""

from simple_distributed_machine_learning_tpu.serve.engine import (  # noqa: F401
    DrainTimeout,
    InferenceEngine,
)
from simple_distributed_machine_learning_tpu.serve.fleet import (  # noqa: F401
    AutoscalePolicy,
    ServeFleet,
)
from simple_distributed_machine_learning_tpu.serve.flight import (  # noqa: F401
    FlightRecorder,
    write_bundle,
)
from simple_distributed_machine_learning_tpu.serve.journal import (  # noqa: F401
    RequestJournal,
)
from simple_distributed_machine_learning_tpu.serve.metrics import (  # noqa: F401
    ServeMetrics,
)
from simple_distributed_machine_learning_tpu.serve.request import (  # noqa: F401
    Request,
)
from simple_distributed_machine_learning_tpu.serve.router import (  # noqa: F401
    FleetRouter,
)
from simple_distributed_machine_learning_tpu.serve.scheduler import (  # noqa: F401
    FCFSScheduler,
    PriorityScheduler,
)
from simple_distributed_machine_learning_tpu.serve.simulator import (  # noqa: F401
    SimConfig,
    TrafficClass,
    simulate,
)
from simple_distributed_machine_learning_tpu.serve.slots import (  # noqa: F401
    PagedKVPool,
)
from simple_distributed_machine_learning_tpu.serve.supervisor import (  # noqa: F401
    OverloadPolicy,
    ServeSupervisor,
    engine_factory,
)
from simple_distributed_machine_learning_tpu.serve.tracing import (  # noqa: F401
    ServeTrace,
)
