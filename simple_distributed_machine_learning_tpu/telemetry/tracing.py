"""The program's span recorder: host spans on the hot paths, kept in a ring
in memory, written into the profiler's trace, exported as Chrome-trace JSON.

One process has one current :class:`Tracer` (:func:`current`, there from
import); the program's hot paths open their spans through the module-level
:func:`span`::

    with tracing.span("engine.prefill.dispatch", rid=r.rid):
        ...

A span records its name, its start and end as absolute
``time.perf_counter_ns()`` (so it joins any other reading of that clock), the
thread, an id of its own and the id of the span that was open on the same
thread when it began (``parent``), and its attributes. Every span also enters
``jax.profiler.TraceAnnotation(name)``: whenever a profiler session runs, the
same spans lie on its host plane beside the device lines. ``tracer.enabled =
False`` is the operator's switch: :func:`span` then returns one shared no-op
object, one attribute test a site.

Two kinds of stall nobody called for are recorded where they happen, once a
process: JAX's trace, lower and backend-compile events (``jax.trace``,
``jax.lower``, ``jax.compile``, from its monitoring listener) and the
collector's pauses (``py.gc``, from ``gc.callbacks``). Both cost nothing
between events, and an event shorter than :data:`STALL_FLOOR_NS` is no stall
and leaves no span: tracing a deep program fires a trace event for every
inner jitted helper (13,485 events in a 36-layer toy's serve set-up, most of
them microseconds), and a minute of such tracing would flush everything else
out of the ring.

:meth:`Tracer.to_chrome_trace` / :meth:`Tracer.write` emit the
``chrome://tracing`` / Perfetto JSON format, so a run with
``--telemetry-dir`` is timeline-inspectable with nothing but a browser. The
async events (:meth:`Tracer.async_begin` ...) carry the serve engine's
request timelines (``serve/tracing.py``), which keeps a private tracer of
its own: those are stamped on the engine's accounting clock, a different
record.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import threading
import time

import jax

DEFAULT_CAPACITY = 65_536
STALL_FLOOR_NS = 1_000_000     # a compile event or collection under 1 ms

# the monitoring events turned into spans (the same three the benchmark
# counts as compiles)
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}

_ids = itertools.count(1)
_open = threading.local()      # .stack: the ids of this thread's open spans


def _stack() -> list[int]:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


class Span:
    """One host interval; a context manager that records itself into its
    tracer's ring when it closes (an exception still closes it: the trace
    must show the failing interval, not lose it)."""

    __slots__ = ("name", "start_ns", "end_ns", "tid", "id", "parent",
                 "attrs", "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict) -> None:
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Add attributes before the span closes (what a tick did is known
        only at its end)."""
        self.attrs.update(attrs)

    def _place(self) -> list[int]:
        """Give the span its id, thread and parent (the span open on this
        thread now); returns the thread's stack of open spans."""
        stack = _stack()
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        self.tid = threading.get_ident()
        return stack

    def __enter__(self) -> "Span":
        self._place().append(self.id)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _stack().pop()
        self._tracer._record(self)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class _NoSpan:
    """What :func:`span` returns while the recorder is disabled."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NO_SPAN = _NoSpan()


class Tracer:
    """Collects completed spans in a ring; thread-safe; ``write`` emits
    Chrome JSON.

    Two event families:

    - :meth:`span` / :meth:`instant` — synchronous host intervals on the
      calling thread's track (``ph: "X"``/``"i"``), stamped from this
      process's ``perf_counter_ns``. Spans live in a ring of ``capacity``
      (``dropped`` counts the evicted, ``evicted_until_ns`` is the latest
      end among them);
    - :meth:`async_begin` / :meth:`async_end` / :meth:`async_instant` —
      Chrome *async* events (``ph: "b"``/``"e"``/``"n"``) keyed by an
      explicit ``(cat, id)`` pair, so arbitrarily overlapping timelines
      (e.g. concurrent serving requests) render as separate tracks instead
      of nesting wrongly by ts containment. Async events accept an explicit
      ``ts_us`` so a caller with its own clock (the serve engine's —
      possibly a :class:`~..resilience.scenarios.VirtualClock`) can stamp
      events without this tracer ever reading a clock itself.

    ``pid`` overrides the recorded process id (``ServeTrace`` pins it to 0
    so virtual-clock traces are byte-identical across runs and machines).
    """

    def __init__(self, process_name: str = "sdml", pid: int | None = None,
                 capacity: int = DEFAULT_CAPACITY) -> None:
        self.enabled = True
        self._t0_ns = time.perf_counter_ns()
        self._spans: collections.deque[Span] = collections.deque(
            maxlen=int(capacity))
        self._recorded = 0
        self.evicted_until_ns = 0
        self._events: list[dict] = []
        # re-entrant: the collector can run (and record its ``py.gc`` span)
        # while this thread is inside ``_record``
        self._lock = threading.RLock()
        self._pid = os.getpid() if pid is None else int(pid)
        self._process_name = process_name

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    # -- spans -------------------------------------------------------------

    def span(self, name: str, **attrs):
        """``with tracer.span("step", epoch=3) as sp: ...`` — one interval;
        ``sp.set(...)`` adds attributes before it closes."""
        if not self.enabled:
            return NO_SPAN
        return Span(self, name, attrs)

    def _record(self, sp: Span) -> None:
        with self._lock:
            ring = self._spans
            if len(ring) == ring.maxlen:
                self.evicted_until_ns = max(self.evicted_until_ns,
                                            ring[0].end_ns)
            ring.append(sp)
            self._recorded += 1

    def record(self, name: str, start_ns: int, end_ns: int, **attrs) -> None:
        """A span whose interval is already known (a duration event, a
        collector pause): its parent is the span open on this thread."""
        if not self.enabled:
            return
        sp = Span(self, name, attrs)
        sp._place()
        sp.start_ns, sp.end_ns = int(start_ns), int(end_ns)
        self._record(sp)

    def spans(self) -> list[Span]:
        """The ring's spans, oldest first (in the order they closed)."""
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        """Spans the ring has evicted."""
        with self._lock:
            return self._recorded - len(self._spans)

    def instant(self, name: str, **attrs) -> None:
        """A zero-duration marker (``ph: "i"``) — epoch boundaries etc."""
        ev = {"name": name, "ph": "i", "s": "t", "ts": self._now_us(),
              "pid": self._pid, "tid": threading.get_ident()}
        if attrs:
            ev["args"] = {k: _jsonable(v) for k, v in attrs.items()}
        with self._lock:
            self._events.append(ev)

    # -- async (overlapping) spans ----------------------------------------

    def _async_event(self, ph: str, name: str, aid, ts_us, cat: str,
                     attrs: dict) -> None:
        ev = {"name": name, "ph": ph, "cat": cat, "id": str(aid),
              "ts": self._now_us() if ts_us is None else float(ts_us),
              "pid": self._pid, "tid": 0}
        if attrs:
            ev["args"] = {k: _jsonable(v) for k, v in attrs.items()}
        with self._lock:
            self._events.append(ev)

    def async_begin(self, name: str, aid, ts_us: float | None = None,
                    cat: str = "async", **attrs) -> None:
        """Open one async span keyed by ``(cat, aid, name)`` (Chrome ``b``
        phase). Overlapping spans with distinct ids never nest into each
        other — the property per-request serve timelines need."""
        self._async_event("b", name, aid, ts_us, cat, attrs)

    def async_end(self, name: str, aid, ts_us: float | None = None,
                  cat: str = "async", **attrs) -> None:
        """Close the matching ``async_begin`` (Chrome ``e`` phase); the
        viewer pairs strictly on ``(cat, id, name)``, never on nesting."""
        self._async_event("e", name, aid, ts_us, cat, attrs)

    def async_instant(self, name: str, aid, ts_us: float | None = None,
                      cat: str = "async", **attrs) -> None:
        """A zero-duration marker on an async track (Chrome ``n`` phase)."""
        self._async_event("n", name, aid, ts_us, cat, attrs)

    # -- export ------------------------------------------------------------

    def to_chrome_trace(self) -> dict:
        """Timestamps are microseconds since this tracer was made (the
        spans' absolute stamps less that), as they always were."""
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"name": self._process_name}}]
        with self._lock:
            spans = list(self._spans)
            events = list(self._events)
        xs = []
        for sp in spans:
            ev = {"name": sp.name, "ph": "X",
                  "ts": (sp.start_ns - self._t0_ns) / 1e3,
                  "dur": (sp.end_ns - sp.start_ns) / 1e3,
                  "pid": self._pid, "tid": sp.tid}
            if sp.attrs:
                ev["args"] = {k: _jsonable(v) for k, v in sp.attrs.items()}
            xs.append(ev)
        return {"traceEvents": meta + xs + events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path`` (atomic rename so a
        reader never sees a torn file) and return the path."""
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path


def _jsonable(v):
    return v if isinstance(v, (int, float, str, bool, type(None))) else str(v)


# -- the process's recorder ---------------------------------------------------

_current = Tracer()


def current() -> Tracer:
    """The process's recorder: what :func:`span` writes to."""
    return _current


def install(tracer: Tracer) -> Tracer:
    """Make ``tracer`` the process's recorder (``Telemetry`` installs its
    own, so ``trace.json`` holds the hot paths' spans); returns the one it
    replaces."""
    global _current
    previous, _current = _current, tracer
    return previous


def span(name: str, **attrs):
    """A span in the process's recorder, or the shared no-op while it is
    disabled."""
    return _current.span(name, **attrs)


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    name = _JAX_EVENTS.get(event)
    duration_ns = int(duration_secs * 1e9)
    if name is not None and duration_ns >= STALL_FLOOR_NS:
        end = time.perf_counter_ns()
        _current.record(name, end - duration_ns, end)


_gc_started_ns = 0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_started_ns
    if phase == "start":
        _gc_started_ns = time.perf_counter_ns()
    elif _gc_started_ns:
        end = time.perf_counter_ns()
        if end - _gc_started_ns >= STALL_FLOOR_NS:
            _current.record("py.gc", _gc_started_ns, end,
                            generation=info.get("generation"))
        _gc_started_ns = 0


# once a process (a module is imported once): the compile and collector
# events become spans of whichever recorder is current
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
gc.callbacks.append(_on_gc)
