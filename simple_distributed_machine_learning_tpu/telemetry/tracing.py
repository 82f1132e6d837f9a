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

``span(name, sched=True, ...)`` also records what the kernel's scheduler did
to the calling thread in the span, as up to four attributes read as it
opens and as it closes (a span so opened takes 8 us against a plain one's
2.5 on a plain Linux host, 17 against 3.2 where a reading is a sandboxed
system call of 6 us: ``PERF.md`` section 6, PR 37; that time is the span's
own where it has a parent, whose children then still cover it, and lies
just outside its two stamps where it has none):
``cpu_ns`` (``time.thread_time_ns()``: CPU time of this thread), ``nvcsw`` /
``nivcsw`` (voluntary / involuntary context switches,
``getrusage(RUSAGE_THREAD)``) and ``runq_ns`` (nanoseconds the thread was
runnable and waited for a CPU: the second field of
``/proc/thread-self/schedstat``, through one descriptor a thread kept open).
A reading this kernel does not give is left out and never asked for again:
``runq_ns`` where the file cannot be opened, the two switch counts where a
sleep does not move them (asked once a process, 1 ms; a sandboxed kernel
counts none). ``cpu_ns`` is as fine as the kernel's clock: a span without
a parent may read its length and a reading's own time more, and where the
clock moves in steps of 10 ms it tells only a long span busy from asleep.
A long span then says of itself which it was: asleep and never woken
(``cpu_ns`` near 0, one voluntary switch, ``runq_ns`` near 0: the
runtime's or the device's),
runnable without a CPU (``runq_ns`` near its length, or ``nivcsw`` > 0: the
machine's, a CPU quota for one), or busy (``cpu_ns`` near its length: the
program's own work). They are exported with the other attributes as the
event's ``args`` in ``trace.json``. The serve engine opens ``engine.tick``
and its ``*.wait`` spans so; a disabled recorder reads none of it.

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
import resource
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
# .stack: the ids of this thread's open spans; .schedstat: its open
# /proc/thread-self/schedstat (None where that cannot be opened)
_open = threading.local()
SCHEDSTAT = "/proc/thread-self/schedstat"


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
        self._close(exc)

    def _close(self, exc) -> None:
        self._annotation.__exit__(*exc)
        self._annotation = None
        _stack().pop()
        self._tracer._record(self)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_SCHED = ("cpu_ns", "nvcsw", "nivcsw", "runq_ns")
_rusage_counts = None     # does getrusage count this kernel's switches?


def _rusage_live() -> bool:
    """Whether ``getrusage(RUSAGE_THREAD)`` counts context switches here:
    asked once a process, across one sleep of 1 ms."""
    global _rusage_counts
    if _rusage_counts is None:
        before = resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw
        time.sleep(0.001)
        after = resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw
        _rusage_counts = after > before
    return _rusage_counts


def _sched_now() -> tuple:
    """The calling thread's readings in the order of ``_SCHED``: its CPU
    time (ns), its voluntary and involuntary context switches, its wait
    for a CPU (ns); ``None`` for one this kernel does not give."""
    try:
        stat = _open.schedstat
    except AttributeError:
        # the link resolves to the opening thread's file: one a thread
        try:
            stat = open(SCHEDSTAT, "rb", buffering=0)
        except OSError:
            stat = None
        _open.schedstat = stat
    nv = niv = None
    if _rusage_live():
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        nv, niv = ru.ru_nvcsw, ru.ru_nivcsw
    runq = (None if stat is None
            else int(os.pread(stat.fileno(), 64, 0).split()[1]))
    return time.thread_time_ns(), nv, niv, runq


class SchedSpan(Span):
    """A span that also records what the scheduler did to its thread
    (``span(name, sched=True)``). What the readings cost has to be some
    span's time: a span with a parent reads just inside its own two
    stamps, so that the parent's children still cover the parent; one
    without reads just outside them, its time being nobody else's."""

    __slots__ = ("_sched",)

    def __enter__(self) -> "SchedSpan":
        if not _stack():
            self._sched = _sched_now()
        super().__enter__()
        if self.parent is not None:
            self._sched = _sched_now()
        return self

    def __exit__(self, *exc) -> None:
        if self.parent is not None:
            now = _sched_now()
            self.end_ns = time.perf_counter_ns()
        else:
            self.end_ns = time.perf_counter_ns()
            now = _sched_now()
        for key, before, after in zip(_SCHED, self._sched, now):
            if after is not None:
                self.attrs[key] = after - before
        self._close(exc)


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

    def span(self, name: str, sched: bool = False, **attrs):
        """``with tracer.span("step", epoch=3) as sp: ...`` — one interval;
        ``sp.set(...)`` adds attributes before it closes; ``sched=True``
        adds what the scheduler did to the thread (:class:`SchedSpan`)."""
        if not self.enabled:
            return NO_SPAN
        return (SchedSpan if sched else Span)(self, name, attrs)

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


def span(name: str, sched: bool = False, **attrs):
    """A span in the process's recorder, or the shared no-op while it is
    disabled."""
    return _current.span(name, sched, **attrs)


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    name = _JAX_EVENTS.get(event)
    duration_ns = int(duration_secs * 1e9)
    if (name is not None and duration_ns >= STALL_FLOOR_NS
            and _current.enabled):
        end = time.perf_counter_ns()
        _current.record(name, end - duration_ns, end)


_gc_started_ns = 0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_started_ns
    if phase == "start":
        # a disabled recorder reads no clock, here as in span()
        _gc_started_ns = time.perf_counter_ns() if _current.enabled else 0
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
