"""What every runner shares: the device check, the compile counter, host
spans on the profiler's clock, the traced sub-window and the memory peak."""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
import time

import jax

TRACE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_cache_read_s",
}


def require_tpu(chips: int) -> dict:
    """Exactly the cell's chips, all TPUs; anything else ends the run with
    no result line (never a CPU run)."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"bench_cells: needs {chips} TPU chip(s); JAX found "
                         f"platform {d0.platform!r} ({d0.device_kind})")
    if len(devs) != chips:
        raise SystemExit(f"bench_cells: the cell asks for {chips} TPU "
                         f"chip(s); JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class Compiles:
    """Seconds JAX spent tracing, lowering and compiling (or reading the
    persistent cache), and how many such events there were: the count taken
    before and after the window shows that nothing compiled inside it."""

    def __init__(self) -> None:
        self.seconds = {v: 0.0 for v in TRACE_EVENTS.values()}
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        key = TRACE_EVENTS.get(event)
        if key is not None:
            self.seconds[key] += duration
            self.events += 1


class Spans:
    """The harness's own spans around its calls into the program: kept in
    memory on the host clock, and written into the profiler's trace (as
    ``TraceAnnotation``) while one is being taken."""

    def __init__(self) -> None:
        self.rows: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.rows.append((name, t0, time.perf_counter()))


class Tracer:
    """A profiler trace over part of the window (``--trace 1``). Traces are
    large and tracing slows the host, so the runner brackets a few steps or
    seconds with :meth:`start`/:meth:`stop`; end-to-end numbers come from
    runs with the profiler off."""

    def __init__(self, enabled: bool) -> None:
        self.enabled = enabled
        self.dir: str | None = None
        self.started_at: float | None = None
        self.window_s: float | None = None

    def start(self) -> None:
        if not self.enabled or self.dir is not None:
            return
        self.dir = tempfile.mkdtemp(prefix="bench_cells_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # our spans are enough; cheaper
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        if self.started_at is None or self.window_s is not None:
            return
        self.window_s = time.perf_counter() - self.started_at
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self.started_at is not None and self.window_s is None

    def xplane_path(self) -> str | None:
        if self.dir is None:
            return None
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return found[0] if found else None

    def cleanup(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, as the backend reports it."""
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    if not peaks:
        raise SystemExit("bench_cells: the backend reports no "
                         "peak_bytes_in_use")
    return max(peaks)
