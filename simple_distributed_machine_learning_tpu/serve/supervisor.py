"""The serve supervisor: crash-restartable serving with overload control.

PR 7 made *training* elastic; this module gives the inference engine the
same production shape.  :class:`ServeSupervisor` wraps an
:class:`~.engine.InferenceEngine` behind the engine's own duck-typed
surface (``submit``/``step``/``drain``/``busy``/``requests``), adding the
three things a single-process engine lacks:

**Crash recovery (RUNNING → RECOVERING → RUNNING | DEGRADED).**  Every
submission and every emitted token is journaled (``serve/journal.py``,
fsync'd, with the request's live PRNG key state riding on each token
record).  A recoverable engine failure — an injected ``engine-crash`` /
``wedged-device`` / ``host-kill`` at the ``serve.tick`` or ``serve.admit``
sites, or anything else in :data:`RECOVERABLE` leaking out of a tick —
discards the engine wholesale, rebuilds a fresh one through the caller's
``factory(degraded)`` and re-admits every in-flight request *from the
journal alone* through the PR-7 preempt/resume machinery: re-admission
prefills ``resume_seq = prompt + tokens[:-1]`` with the sample and key
advance discarded, then reseats on the last journaled token with the
journaled key state — so a request's full token stream equals the
uninterrupted run's, across any number of restarts (double crashes, i.e.
a crash during recovery, included).  ``max_restarts`` bounds the loop
(:class:`~..resilience.supervisor.RestartBudgetExceeded`), and
``degrade_after`` restarts flips later rebuilds to the DEGRADED layout —
:func:`engine_factory`'s rule: speculation off, tensor parallelism off,
the fused kernel, a quantised cache and the host tier off, the paged pool
kept (the same transform ``analysis.programs.degraded_spec`` keeps
lint-clean in the program registry).

**Deadlines.**  ``submit(..., ttft_deadline_s=, deadline_s=)`` (or the
supervisor-wide defaults) bound time-to-first-token and total latency.
Expired requests are shed at tick boundaries with a structured rejection
(``state = SHED``, ``finish_reason = "deadline"``) and their slot/block
budget refunded the same release path retirement uses — an expired
request never occupies capacity a live one could use.

**Overload control.**  :class:`OverloadPolicy` gates admission before the
engine sees a request: per-class token buckets (``class_rates``) police
each tenant's arrival rate, ``max_queue_depth`` bounds the queue (a
higher-priority arrival sheds the lowest-priority newest queued victim
first; otherwise the arrival itself is shed), and sustained overload
(queue depth past ``degrade_queue_depth``, with hysteresis) enters the
load-degraded mode where best-effort traffic (priority ≤
``degraded_priority_floor``) is refused outright — graceful degradation
before any SLO class starves.  Every shed lands in
``serve_shed_total{reason=deadline|backpressure|class}``.

Delivery semantics across a crash: the token LIST on a handle is
exactly-once (recovery truncates to the journaled prefix and the decode
re-emits the identical tokens); the ``on_token`` callback is at-least-once
at crash boundaries (a token emitted between the journal write and the
client ack replays).  Sampled SPECULATIVE streams add one caveat: a
multi-token speculative tick journals under the tick's single key state,
so their cold-restart recovery is tick-atomic (``journal.py::log_token``'s
caveat note) — every in-process recovery and every greedy stream is
unconditionally bit-exact.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from simple_distributed_machine_learning_tpu.resilience.faults import (
    DeviceWedged,
    EngineCrash,
    HostLost,
)
from simple_distributed_machine_learning_tpu.serve.journal import (
    RequestJournal,
)
from simple_distributed_machine_learning_tpu.serve.request import (
    ACTIVE,
    DONE,
    QUEUED,
    SHED,
    Request,
    validate_request,
)

# supervisor states (the machine in the module docstring / ARCHITECTURE.md)
RUNNING = "running"
RECOVERING = "recovering"
DEGRADED = "degraded"
FAILED = "failed"

#: engine failures the supervisor restarts through — the engine (pool
#: buffers + host bookkeeping) is rebuilt from scratch and in-flight
#: requests recover from the journal.  Anything else is a bug in the
#: serving code and propagates un-retried.
RECOVERABLE = (EngineCrash, DeviceWedged, HostLost)


@dataclasses.dataclass(frozen=True)
class OverloadPolicy:
    """Admission-control knobs; ``OverloadPolicy()`` disables them all.

    ``class_rates`` maps a traffic-class name to a ``(rate_per_s, burst)``
    token bucket — submissions beyond the bucket shed with reason
    ``"class"``.  ``max_queue_depth`` bounds the scheduler queue: at the
    bound, an arrival strictly higher-priority than some queued request
    sheds the lowest-priority newest-queued victim (reason
    ``"backpressure"``) and boards; otherwise the arrival itself sheds.
    ``degrade_queue_depth``/``recover_queue_depth`` are the load-degraded
    hysteresis: past the high watermark, requests at priority ≤
    ``degraded_priority_floor`` are refused (reason ``"class"``) until the
    queue drains to the low watermark."""

    max_queue_depth: int | None = None
    class_rates: dict | None = None
    degrade_queue_depth: int | None = None
    recover_queue_depth: int = 0
    degraded_priority_floor: int = 0

    def __post_init__(self):
        if self.class_rates is not None:
            # defensive copy, normalized to plain tuples: ONE policy
            # instance is routinely shared by N supervisors (the fleet's
            # replica factory), so the stored mapping must not alias a
            # caller dict whose later mutation would silently retune — or
            # couple — every replica's admission control. Each supervisor
            # still keeps its own PER-INSTANCE bucket fills (_buckets);
            # tests/test_fleet.py pins that one replica's debit never
            # appears in another's.
            object.__setattr__(
                self, "class_rates",
                {cls: (float(rb[0]), float(rb[1]))
                 for cls, rb in self.class_rates.items()})
        if self.max_queue_depth is not None and self.max_queue_depth < 1:
            raise ValueError(f"max_queue_depth must be >= 1, got "
                             f"{self.max_queue_depth}")
        if self.degrade_queue_depth is not None:
            if self.degrade_queue_depth < 1:
                raise ValueError(f"degrade_queue_depth must be >= 1, got "
                                 f"{self.degrade_queue_depth}")
            if self.recover_queue_depth >= self.degrade_queue_depth:
                raise ValueError(
                    f"recover_queue_depth {self.recover_queue_depth} must "
                    f"sit below degrade_queue_depth "
                    f"{self.degrade_queue_depth} (hysteresis, not a "
                    f"flapping threshold)")
        for cls, rb in (self.class_rates or {}).items():
            rate, burst = rb
            if rate <= 0 or burst < 1:
                raise ValueError(
                    f"class {cls!r}: token bucket needs rate > 0 and "
                    f"burst >= 1, got ({rate}, {burst})")


def engine_factory(stages, cfg, *, metrics=None, clock=time.monotonic,
                   scheduler=None, mesh=None, draft_stages=None,
                   draft_cfg=None, spec_k: int = 0,
                   adapter_rank: int = 0, adapter_host: dict | None = None,
                   **kw):
    """The standard ``factory(degraded) -> InferenceEngine`` closure.

    Non-degraded builds get the full deployment (pool knobs, TP mesh,
    speculative draft) exactly as passed; ``degraded=True`` applies the
    fallback rule "drop speed features, never tenants": ``spec_k → 0``,
    ``tp → 1``, the gather-then-dense attention in place of the fused
    kernel, a quantised ``cache_dtype`` widened to f32, the host tier and
    its prefetch off — and the paged pool KEPT (``block_size``,
    ``n_blocks``, ``prefill_chunk`` as passed), so the fallback builds for
    every model the engine serves, one with recurrent state included.
    ``analysis.programs.degraded_spec`` mirrors the rule so the program
    registry proves the fallback lint-clean before any crash needs it.
    The fallback stays bit-exact for everything except *sampled* requests
    that were being served speculatively (plain-decode streams equal the
    solo decode whatever the kernel; sampled speculative streams are
    deterministic but consume the key streams differently).

    ``adapter_rank > 0`` turns on multi-tenant LoRA serving: every build
    (degraded ones included) gets a FRESH :class:`~.adapters.AdapterStore` over one
    SHARED ``adapter_host`` dict, so registered adapters survive crash
    rebuilds while device residency honestly resets with the engine.

    ``scheduler`` must be a CLASS/factory (each rebuilt engine constructs
    its own instance over its own pool); ``metrics``/``clock`` are shared
    across rebuilds so counters and timelines stay continuous.
    """
    from simple_distributed_machine_learning_tpu.serve.engine import (
        InferenceEngine,
    )
    if adapter_rank > 0 and adapter_host is None:
        adapter_host = {}        # one dict across every rebuild

    def _adapter_kw(n_slots: int) -> dict:
        if adapter_rank <= 0:
            return {}
        from simple_distributed_machine_learning_tpu.serve.adapters import (
            AdapterStore,
        )
        return {"adapters": AdapterStore(cfg, adapter_rank, n_slots,
                                         host=adapter_host)}

    def factory(degraded: bool) -> InferenceEngine:
        n_slots = kw.get("n_slots", 4)
        if not degraded:
            return InferenceEngine(
                stages, cfg, metrics=metrics, clock=clock,
                scheduler=scheduler, mesh=mesh, draft_stages=draft_stages,
                draft_cfg=draft_cfg, spec_k=spec_k,
                **_adapter_kw(n_slots), **kw)
        dcfg = cfg
        if getattr(cfg, "n_tensor_parallel", 1) > 1:
            dcfg = dataclasses.replace(cfg, n_tensor_parallel=1)
        dkw = {k: v for k, v in kw.items()
               if k not in ("attn_kernel", "host_cache_blocks",
                            "prefetch_ticks")}
        from simple_distributed_machine_learning_tpu.models.serving import (
            is_quantized_dtype,
        )
        if is_quantized_dtype(dkw.get("cache_dtype")):
            # the fallback widens a quantized pool to f32 — same rule
            # degraded_spec mirrors for the lint gate
            dkw["cache_dtype"] = None
        return InferenceEngine(stages, dcfg, metrics=metrics, clock=clock,
                               scheduler=scheduler,
                               **_adapter_kw(n_slots), **dkw)

    return factory


class ServeSupervisor:
    """Crash-restartable, deadline- and overload-aware serving; see the
    module docstring.  Duck-types the engine surface the simulator and the
    scenario runner drive (``submit``/``step``/``drain``/``busy``/
    ``requests``/``metrics``/``cfg``/``_clock``)."""

    def __init__(self, factory, journal, *, metrics=None,
                 clock=time.monotonic, max_restarts: int = 3,
                 degrade_after: int | None = None,
                 overload: OverloadPolicy | None = None,
                 default_ttft_deadline_s: float | None = None,
                 default_deadline_s: float | None = None,
                 trace=None, flight=None, postmortem_dir: str | None = None,
                 postmortem_tail: int = 64, shed_burst: int = 4,
                 postmortem_tag: str = "", slo=None) -> None:
        if max_restarts < 0:
            raise ValueError(f"max_restarts must be >= 0, got "
                             f"{max_restarts}")
        if degrade_after is not None and degrade_after < 1:
            raise ValueError(f"degrade_after must be >= 1 restarts, got "
                             f"{degrade_after}")
        if shed_burst < 1:
            raise ValueError(f"shed_burst must be >= 1, got {shed_burst}")
        self.factory = factory
        self.journal = (RequestJournal(journal) if isinstance(journal, str)
                        else journal)
        self.metrics = metrics
        self._clock = clock
        self.max_restarts = int(max_restarts)
        self.degrade_after = degrade_after
        self.overload = overload if overload is not None else OverloadPolicy()
        self.default_ttft_deadline_s = default_ttft_deadline_s
        self.default_deadline_s = default_deadline_s
        # observability (ISSUE 12): the request-scoped trace recorder
        # (re-attached to every rebuilt engine, which is what joins spans
        # across restarts), the tick flight recorder, and the post-mortem
        # bundle sink. All off by default; the flight recorder is created
        # implicitly when bundles are requested (a bundle without flight
        # rows is a crash report with no flight data).
        self.trace = trace
        self.postmortem_dir = postmortem_dir
        self.postmortem_tail = int(postmortem_tail)
        # bundle filename infix — the FLEET sets "-r<idx>" per replica so
        # N supervisors sharing one postmortem_dir never overwrite each
        # other's postmortem-000-* names
        self.postmortem_tag = postmortem_tag
        self.shed_burst = int(shed_burst)
        if flight is None and postmortem_dir is not None:
            from simple_distributed_machine_learning_tpu.serve.flight import (
                FlightRecorder,
            )
            flight = FlightRecorder()
        self.flight = flight
        # streaming SLO engine (telemetry/slo.py): evaluated once per
        # supervised tick AT self.tick (never from a clock), so alert
        # transitions are exact-pinnable under the virtual clock. Under a
        # fleet the FLEET owns evaluation (one engine across replicas,
        # evaluated at fleet.tick) and clears _drive_slo on every replica.
        self.slo = slo
        self._drive_slo = True
        if slo is not None and metrics is not None:
            metrics.bind_slo(slo)
        self.postmortems: list[str] = []     # bundle paths, write order
        self._sheds_since_step = 0
        # disaggregated-fleet role ("prefill" | "decode"; None outside a
        # disaggregated fleet) — set by ServeFleet, stamped onto every
        # flight-recorder row so post-mortems localize WHICH pool saturated
        self.pool_role: str | None = None
        #: monotonic tick counter — unlike ``engine._tick_count`` it
        #: survives engine rebuilds, and it is the ``tick`` every journal
        #: record and flight-recorder row carries (the forensic join key)
        self.tick = 0
        self.restarts = 0
        self.degraded = False        # fault-driven: rebuilds use the fallback
        self.load_degraded = False   # overload-driven: best-effort lockout
        self.state = RUNNING
        self.requests: dict[int, Request] = {}
        self._open: set[int] = set()           # submitted, not DONE/SHED
        self._user_cb: dict[int, object] = {}  # rid -> caller's on_token
        self._buckets: dict[str, tuple[float, float]] = {}
        self.engine = factory(False)
        self._attach_engine(prev_now=0.0)
        # cold start: a previous process's journal recovers here — its
        # completed streams become readable handles, its in-flight requests
        # re-admit and continue bit-exact (no restart consumed: the budget
        # guards THIS process's engine, not history)
        snapshots = self.journal.recovered_state()
        if snapshots:
            self._reseat(snapshots, note_recovered=True)

    def _attach_engine(self, prev_now: float) -> None:
        """Wire the (re)built engine into the shared observability state:
        the trace recorder outlives engines — that is what joins a
        request's spans across incarnations — and the new engine's
        last-read-clock seed carries over so post-crash trace stamps stay
        monotonic (never a fresh clock read)."""
        if self.trace is not None:
            self.engine.trace = self.trace
        self.engine._now = max(self.engine._now, prev_now)

    # -- the engine surface -------------------------------------------------

    @property
    def busy(self) -> bool:
        return self.engine.busy

    @property
    def cfg(self):
        return self.engine.cfg

    @property
    def scheduler(self):
        return self.engine.scheduler

    @property
    def pool(self):
        return self.engine.pool

    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               top_k: int | None = None, top_p: float | None = None,
               eos_id: int | None = None, seed: int | None = None,
               on_token=None, arrival_time: float | None = None,
               cls: str | None = None, priority: int = 0,
               ttft_deadline_s: float | None = None,
               deadline_s: float | None = None,
               adapter: str | None = None) -> Request:
        """Admission-controlled, journaled submit.  The returned handle may
        already be ``SHED`` (a structured rejection — the request never
        reached the engine); otherwise the submission is journaled BEFORE
        the engine sees it, so even a crash inside admission recovers it."""
        now = self._clock() if arrival_time is None else arrival_time
        if ttft_deadline_s is None:
            ttft_deadline_s = self.default_ttft_deadline_s
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        prompt = np.asarray(prompt, np.int32)
        # validate BEFORE journaling: a rejected submission must not leave
        # a journal entry recovery would forever fail to re-admit (the
        # adapter check included — an unregistered tenant must fail here,
        # not as a poisoned `adp` record)
        validate_request(prompt, max_new_tokens, temperature, top_k, top_p,
                         self.engine.cfg.vocab, self.engine.max_len)
        self.engine._check_adapter(adapter)
        rid = self.engine._next_rid      # the rid engine.submit will assign
        seed = rid if seed is None else seed
        reason = self._admission_check(cls, priority, now)
        if reason is not None:
            return self._shed_at_admission(
                rid, prompt, max_new_tokens, temperature, top_k, top_p,
                eos_id, seed, cls, priority, ttft_deadline_s, deadline_s,
                reason, now, adapter=adapter)
        self._user_cb[rid] = on_token
        self.journal.log_submit(
            rid=rid, prompt=prompt, max_new=max_new_tokens,
            temp=temperature, top_k=top_k, top_p=top_p, eos=eos_id,
            seed=seed, cls=cls, prio=priority, ttft_dl=ttft_deadline_s,
            dl=deadline_s, t=now, tick=self.tick, adapter=adapter)
        try:
            r = self.engine.submit(
                prompt, max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, eos_id=eos_id, seed=seed,
                on_token=self._on_token, arrival_time=now, cls=cls,
                priority=priority, ttft_deadline_s=ttft_deadline_s,
                deadline_s=deadline_s, adapter=adapter)
        except RECOVERABLE as e:
            # the serve.admit crash: the journal already carries this
            # submission, so recovery rebuilds and re-admits it
            self._recover(e)
            return self.requests[rid]
        assert r.rid == rid, (r.rid, rid)
        self.requests[rid] = r
        self._open.add(rid)
        return r

    def register_adapter(self, name: str, weights: dict) -> None:
        """Add or hot-swap a named LoRA adapter (host-side; the next
        admission of the name uploads it at a tick boundary). Registration
        lands in the factory's SHARED host dict, so it survives crash
        rebuilds — a recovered request re-admits onto the same tenant."""
        store = getattr(self.engine, "_adapters", None)
        if store is None:
            raise ValueError(
                "this supervisor's engine was built without an "
                "AdapterStore — pass adapter_rank= to engine_factory")
        store.register(name, weights)

    def step(self) -> int:
        """One supervised tick: deadline shedding, then the engine tick
        (recoverable failures recover in place), then completion acks.
        Each call advances the MONOTONIC :attr:`tick` (journal records and
        flight-recorder rows both carry it), records one flight snapshot,
        and dumps a post-mortem bundle when this tick shed a burst."""
        self.tick += 1
        self._shed_expired()
        try:
            emitted = self.engine.step()
        except RECOVERABLE as e:
            self._recover(e)
            emitted = 0
        self._ack_done()
        self._update_load_degraded()   # a draining backlog lifts the mode
        #                                even if no further arrival probes it
        if self.metrics is not None:
            self.metrics.set_journal_bytes(self.journal.bytes)
        if self.slo is not None and self._drive_slo:
            # evaluate BEFORE the flight snapshot so the row at tick T
            # carries the alert set as of the evaluation at T (the
            # bundle/journal tick-join contract)
            self.slo.evaluate(self.tick)
        if self.flight is not None:
            self.flight.snap(self.engine, self.tick, emitted,
                             state=self.state, restarts=self.restarts,
                             degraded=self.degraded,
                             load_degraded=self.load_degraded,
                             **({} if self.pool_role is None
                                else {"pool_role": self.pool_role}),
                             **({} if self.slo is None
                                else {"active_alerts":
                                      self.slo.active_alerts()}))
        if self._sheds_since_step >= self.shed_burst:
            self._dump_postmortem(
                "shed_burst", f"{self._sheds_since_step} sheds in one tick")
        self._sheds_since_step = 0
        return emitted

    def drain(self, max_ticks: int | None = None) -> list[Request]:
        from simple_distributed_machine_learning_tpu.serve.engine import (
            DrainTimeout,
        )
        ticks = 0
        while self.busy:
            if max_ticks is not None and ticks >= max_ticks:
                exc = DrainTimeout(max_ticks, [
                    r for r in self.requests.values()
                    if r.state in (QUEUED, ACTIVE)])
                # the wedged-drain forensics: what was still queued/active,
                # what the last N ticks looked like, what the journal last
                # saw — dumped BEFORE the raise so the bundle exists even
                # when the caller dies on the exception
                self._dump_postmortem("drain_timeout", str(exc))
                raise exc
            self.step()
            ticks += 1
        return [r for r in self.requests.values() if r.state == DONE]

    def close(self) -> None:
        self.journal.close()
        if self.trace is not None:
            self.trace.flush()

    # -- post-mortem bundles ------------------------------------------------

    def _dump_postmortem(self, trigger: str, cause: str) -> str | None:
        """Write one post-mortem bundle (``serve/flight.py::write_bundle``)
        into ``postmortem_dir``: last-N flight rows + every request's state
        + a metrics snapshot + the journal tail, joined on rid and the
        monotonic tick. No-op without a configured directory."""
        if self.postmortem_dir is None:
            return None
        from simple_distributed_machine_learning_tpu.serve.flight import (
            BUNDLE_PREFIX,
            write_bundle,
        )
        path = os.path.join(
            self.postmortem_dir,
            f"{BUNDLE_PREFIX}{self.postmortem_tag}"
            f"-{len(self.postmortems):03d}-{trigger}.json")
        write_bundle(
            path, trigger=trigger, cause=cause, tick=self.tick,
            flight=self.flight, requests=self.requests,
            registry=(self.metrics.registry
                      if self.metrics is not None else None),
            journal_tail=self.journal.tail(self.postmortem_tail),
            restarts=self.restarts, degraded=self.degraded,
            state=self.state,
            **({} if self.slo is None
               else {"active_alerts": self.slo.active_alerts()}))
        self.postmortems.append(path)
        return path

    # -- overload control ---------------------------------------------------

    def _admission_check(self, cls, priority: int, now: float) -> str | None:
        """The shed reason for this arrival, or None to admit.  May itself
        shed a queued lower-priority victim to make room.  The class
        bucket is PEEKED first but debited only once every other gate
        passed — an arrival shed for backpressure must not charge its
        class for capacity it never used."""
        ov = self.overload
        self._update_load_degraded()
        if self.load_degraded and priority <= ov.degraded_priority_floor:
            return "class"
        if not self._bucket_peek(cls, now):
            return "class"
        if (ov.max_queue_depth is not None
                and self.engine.scheduler.queue_depth >= ov.max_queue_depth):
            victim = self._backpressure_victim(priority)
            if victim is None:
                return "backpressure"
            self._shed_live(victim, "backpressure")
        self._bucket_debit(cls)
        return None

    def _update_load_degraded(self) -> None:
        """The load-degraded hysteresis, from the CURRENT queue depth —
        called at admission AND every tick, so the mode cannot latch on
        after the backlog drains just because arrivals stopped."""
        ov = self.overload
        if ov.degrade_queue_depth is None:
            return
        qd = self.engine.scheduler.queue_depth
        if not self.load_degraded and qd >= ov.degrade_queue_depth:
            self.load_degraded = True
            self._note_degraded()
        elif self.load_degraded and qd <= ov.recover_queue_depth:
            self.load_degraded = False
            self._note_degraded()

    def _bucket_peek(self, cls, now: float) -> bool:
        """Refill the class's bucket to ``now`` and report affordability
        WITHOUT consuming — the refill is monotone so storing it early is
        harmless, the debit is not."""
        rates = self.overload.class_rates
        if not rates or cls not in rates:
            return True
        rate, burst = rates[cls]
        tokens, last = self._buckets.get(cls, (float(burst), now))
        tokens = min(float(burst), tokens + max(0.0, now - last) * rate)
        self._buckets[cls] = (tokens, now)
        return tokens >= 1.0

    def _bucket_debit(self, cls) -> None:
        rates = self.overload.class_rates
        if not rates or cls not in rates:
            return
        tokens, last = self._buckets[cls]
        self._buckets[cls] = (tokens - 1.0, last)

    def _backpressure_victim(self, priority: int) -> Request | None:
        """Lowest-priority, newest-queued request STRICTLY below the
        arrival's priority — the cheapest work to discard for room."""
        best = None
        for r in self.engine.scheduler.queue:
            if r.priority >= priority:
                continue
            if best is None or (r.priority, -r.rid) < (best.priority,
                                                       -best.rid):
                best = r
        return best

    def _shed_expired(self) -> None:
        """Deadline enforcement at the tick boundary: TTFT deadlines bind
        until the first token, total deadlines bind until completion.
        Shedding refunds the slot/block budget immediately (engine.cancel
        routes through the same release path as retirement)."""
        if not any(
                self.requests[rid].deadline_s is not None
                or self.requests[rid].ttft_deadline_s is not None
                for rid in self._open):
            return
        now = self._clock()
        for rid in sorted(self._open):
            r = self.requests[rid]
            if r.state not in (QUEUED, ACTIVE):
                continue
            expired = (
                (r.deadline_s is not None
                 and now - r.submit_time >= r.deadline_s)
                or (r.ttft_deadline_s is not None
                    and r.first_token_time is None
                    and now - r.submit_time >= r.ttft_deadline_s))
            if expired:
                self._shed_live(r, "deadline")

    def _shed_live(self, r: Request, reason: str) -> None:
        self.engine.cancel(r.rid, reason)     # emits the trace shed event
        self.journal.log_shed(rid=r.rid, reason=reason, t=r.done_time,
                              tick=self.tick)
        self._open.discard(r.rid)
        self._user_cb.pop(r.rid, None)
        self._sheds_since_step += 1
        if self.metrics is not None:
            self.metrics.on_shed(reason, cls=r.cls)

    def _shed_at_admission(self, rid, prompt, max_new, temperature, top_k,
                           top_p, eos_id, seed, cls, priority, ttft_dl, dl,
                           reason: str, now: float,
                           adapter: str | None = None) -> Request:
        """A structured rejection: the handle exists (state SHED, the
        reason in ``finish_reason``) but the engine never saw the request.
        The rid is consumed so the journal's id space stays unique, and
        both records land so a cold recovery accounts for it."""
        assert rid == self.engine._next_rid
        self.engine._next_rid = rid + 1
        r = Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    eos_id=eos_id, seed=seed, cls=cls, priority=priority,
                    ttft_deadline_s=ttft_dl, deadline_s=dl, adapter=adapter)
        r.submit_time = now
        r.done_time = now
        r.state = SHED
        r.finish_reason = reason
        self.journal.log_submit(
            rid=rid, prompt=prompt, max_new=max_new, temp=temperature,
            top_k=top_k, top_p=top_p, eos=eos_id, seed=seed, cls=cls,
            prio=priority, ttft_dl=ttft_dl, dl=dl, t=now, tick=self.tick,
            adapter=adapter)
        self.journal.log_shed(rid=rid, reason=reason, t=now, tick=self.tick)
        self.requests[rid] = r
        self._sheds_since_step += 1
        if self.metrics is not None:
            self.metrics.on_submit()
            self.metrics.on_shed(reason, cls=cls)
        if self.trace is not None:
            # the engine never saw this request: open AND close its span
            # here so the timeline still accounts for the rejection
            self.trace.on_submit(r, now)
            self.trace.on_shed(r, now, reason)
        return r

    # -- cross-replica migration (serve/fleet.py) ----------------------------

    def adopt(self, request: Request, on_token=None,
              reason: str = "failure") -> Request:
        """Adopt a request migrated from ANOTHER replica.

        Two callers, one move: failure migration (the source replica's
        host died; ``reason="failure"``, the default) and the
        disaggregated fleet's planned prefill->decode handoff
        (``reason="handoff"`` — the source released the request with
        :meth:`release`). The full snapshot is journaled here FIRST (one
        ``snap`` record carrying the cause under its ``why`` key,
        ``journal.py::log_snapshot``) so THIS replica's journal alone
        recovers the adoptee — a later crash of this replica, or a second
        replica loss on top of the first, replays it exactly like a native
        submission. An in-flight snapshot then re-admits through
        ``engine.restore`` (the same preempt/resume path crash recovery
        uses, so the continued decode stays bit-exact); a DONE/SHED
        snapshot is adopted as a readable handle only. ``on_token`` is the
        CALLER's streaming callback (the source replica's wiring died —
        or was released — with it)."""
        if request.rid in self.requests:
            raise ValueError(
                f"request {request.rid} already lives in this replica — "
                f"adopt() is for migrated rids, which are fleet-unique")
        if request.state not in (QUEUED, DONE, SHED):
            raise ValueError(
                f"request {request.rid} is {request.state!r} — migration "
                f"adopts journal snapshots (queued/done/shed), never a "
                f"live engine's state")
        request.snap_reason = reason
        self.journal.log_snapshot(request, tick=self.tick, reason=reason)
        self.requests[request.rid] = request
        if request.state == QUEUED:
            request.on_token = self._on_token
            self._user_cb[request.rid] = on_token
            self.engine.restore(request)
            self._open.add(request.rid)
        else:
            # finished exactly at the loss boundary: keep the rid space
            # clear of it (restore() was never called to bump it)
            self.engine._next_rid = max(self.engine._next_rid,
                                        request.rid + 1)
        return request

    def release(self, rid: int, dst=None, seal: bool = True) -> Request:
        """Hand a LIVE request out of this replica — the source half of
        the disaggregated fleet's prefill->decode handoff (the adopting
        replica runs :meth:`adopt` with ``reason="handoff"``).

        An ACTIVE request's slot and K/V blocks free immediately (the
        preemption release path, so the handle carries its emitted tokens
        and untouched key stream — re-admission on the destination
        recomputes ``resume_seq`` and continues bit-exact); a QUEUED one
        just leaves the queue. A ``handoff`` journal record marks the rid
        as moved (``journal.py``): recovery of THIS journal drops it, so
        losing this replica later can never double-serve the request.
        Returns the handle (state QUEUED) for the destination to adopt.

        ``seal=False`` defers the terminal ``handoff`` record to a later
        :meth:`seal_handoff` — the copy-then-tombstone ordering the fleet
        uses: journaling the tombstone here, BEFORE the destination's
        ``adopt`` snap lands, opens a window where the rid lives in NO
        journal, so a crash between the two appends loses the request
        (the model checker's ``protocol.lost-request`` counterexample,
        analysis/protocol.py::LEGACY_ORDER)."""
        r = self.requests.get(rid)
        if r is None:
            raise ValueError(f"request {rid} does not live in this replica")
        if r.state not in (QUEUED, ACTIVE):
            raise ValueError(
                f"request {rid} is {r.state!r} — only live "
                f"(queued/active) requests hand off")
        if r.state == ACTIVE:
            # the preempt release path WITHOUT the preemption accounting
            # (a planned handoff is not SLO-protective eviction): slot and
            # blocks free now, state back to QUEUED with tokens intact
            try:
                self.engine._prefilling.remove(rid)   # may be mid-prefill
            except ValueError:
                pass
            self.engine.pool.unbind_seq(r.slot)
            self.engine.pool.release(r.slot)
            r.slot = None
            r.prefill_pos = None
            r.state = QUEUED
        else:
            # identity scan, not deque.remove (Request.__eq__ compares
            # prompt arrays — engine.cancel's same caveat)
            for i, q in enumerate(self.engine.scheduler.queue):
                if q is r:
                    del self.engine.scheduler.queue[i]
                    break
            else:               # pragma: no cover - state-machine guard
                raise RuntimeError(
                    f"queued request {rid} missing from the scheduler "
                    f"queue — lifecycle bookkeeping corrupted")
        del self.engine.requests[rid]
        self.engine._last_emit.pop(rid, None)
        del self.requests[rid]
        self._user_cb.pop(rid, None)
        self._open.discard(rid)
        r.on_token = None        # the destination's adopt() rewires it
        if seal:
            self.journal.log_handoff(rid=rid, dst=dst, tick=self.tick)
        return r

    def seal_handoff(self, rid: int, dst=None) -> None:
        """Journal the terminal ``handoff`` tombstone for a rid this
        replica already released with ``seal=False`` — called by the fleet
        AFTER the destination's ``adopt`` journaled its snap, so at every
        crash point the rid is recoverable from at least one journal (and
        from at most one once this lands)."""
        if rid in self.requests:
            raise ValueError(
                f"request {rid} still lives in this replica — seal only "
                f"what release() already detached")
        self.journal.log_handoff(rid=rid, dst=dst, tick=self.tick)

    # -- crash recovery -----------------------------------------------------

    def _on_token(self, request: Request, token: int) -> None:
        """Every engine token flows through here: journal first (the
        durability point), then the caller's callback — 'journaled but not
        acked' is the recoverable order, the reverse would lose tokens."""
        self.journal.log_token(request, token, tick=self.tick)
        cb = self._user_cb.get(request.rid)
        if cb is not None:
            cb(request, token)

    def _ack_done(self) -> None:
        for rid in list(self._open):
            r = self.requests[rid]
            if r.state == DONE:
                self.journal.log_done(rid=rid, reason=r.finish_reason,
                                      t=r.done_time, tick=self.tick)
                self._open.discard(rid)
                self._user_cb.pop(rid, None)

    def _note_degraded(self) -> None:
        if self.metrics is not None:
            self.metrics.set_degraded(self.degraded or self.load_degraded)
        if self.state in (RUNNING, DEGRADED):
            self.state = (DEGRADED if (self.degraded or self.load_degraded)
                          else RUNNING)

    def _recover(self, exc: BaseException) -> None:
        """RECOVERING: count the restart against the budget, rebuild the
        engine (degraded once past ``degrade_after``) and re-admit every
        in-flight request from the journal alone."""
        from simple_distributed_machine_learning_tpu.resilience.supervisor import (  # noqa: E501
            RestartBudgetExceeded,
        )
        self.state = RECOVERING
        self.restarts += 1
        # the dead engine's last clock reading: every crash-boundary trace
        # stamp (and the rebuilt engine's seed) uses it — recovery must
        # not read the clock, or virtual-clock pins would move
        prev_now = self.engine._now
        if self.restarts > self.max_restarts:
            self.state = FAILED
            self._dump_postmortem("restart_budget",
                                  f"{type(exc).__name__}: {exc}")
            raise RestartBudgetExceeded(
                f"{self.restarts} engine failures exceed the max_restarts="
                f"{self.max_restarts} budget; last: "
                f"{type(exc).__name__}: {exc}") from exc
        if (self.degrade_after is not None and not self.degraded
                and self.restarts >= self.degrade_after):
            self.degraded = True
        if self.metrics is not None:
            self.metrics.on_restart()
        self.journal.log_restart(self.restarts, self.degraded,
                                 type(exc).__name__, tick=self.tick)
        if self.trace is not None:
            self.trace.on_crash(
                prev_now,
                [rid for rid in self._open
                 if self.requests[rid].state in (QUEUED, ACTIVE)],
                type(exc).__name__)
        # the moment-of-failure forensics, BEFORE anything is rebuilt:
        # the dead incarnation's flight rows, its request states, the
        # journal tail — what a post-mortem actually reads
        self._dump_postmortem("restart",
                              f"{type(exc).__name__}: {exc}")
        # journal-ONLY reconstruction: nothing of the dead engine's memory
        # is trusted — exactly the host-kill discipline the trainer has
        snapshots = self.journal.recovered_state()
        self.engine = self.factory(self.degraded)
        self._attach_engine(prev_now=prev_now)
        if self.trace is not None:
            self.trace.on_restart(prev_now, self.restarts, self.degraded,
                                  type(exc).__name__)
        self._reseat(snapshots, note_recovered=True)
        self.state = RUNNING
        self._note_degraded()    # RUNNING -> DEGRADED when a mode is on

    def _reseat(self, snapshots: dict[int, Request],
                note_recovered: bool) -> None:
        """Apply journal snapshots to the live handles (or adopt the
        snapshots as handles on a cold start) and re-admit the in-flight
        ones into ``self.engine`` in rid order — FCFS arrival order
        survives the restart."""
        if snapshots:
            # the rebuilt engine's rid space must clear EVERY journaled rid
            # (done/shed ones included — restore() only bumps past the
            # re-admitted), or a fresh submission would reuse a dead rid
            self.engine._next_rid = max(self.engine._next_rid,
                                        max(snapshots) + 1)
        inflight = []
        for rid in sorted(snapshots):
            snap = snapshots[rid]
            r = self.requests.get(rid)
            if r is None:
                r = snap                     # cold start / mid-submit crash
                self.requests[rid] = r
            else:
                self._apply_snapshot(r, snap)
            if r.state == QUEUED:
                inflight.append(r)
            elif rid in self._open:
                # finished/shed exactly at the crash boundary: the stream
                # is already complete and identical — ack it now
                if r.state == DONE:
                    self.journal.log_done(rid=rid, reason=r.finish_reason,
                                          t=r.done_time, tick=self.tick)
                self._open.discard(rid)
                self._user_cb.pop(rid, None)
        for r in inflight:
            r.on_token = self._on_token
            self.engine.restore(r)
            self._open.add(r.rid)
        if note_recovered and inflight and self.metrics is not None:
            self.metrics.on_recovered(len(inflight))

    @staticmethod
    def _apply_snapshot(r: Request, snap: Request) -> None:
        """Overwrite a live handle's decode state with the journal's —
        object identity is preserved (the caller's handle stays live), the
        STATE is the journal's: tokens truncate to the journaled prefix
        (the decode re-emits the identical tail), key streams rewind to
        the last durable token's."""
        r.tokens[:] = snap.tokens
        r.key_data = snap.key_data
        r.draft_key_data = snap.draft_key_data
        r.submit_time = snap.submit_time
        r.first_token_time = snap.first_token_time
        r.slot = None
        r.prefill_pos = None
        r.state = snap.state
        r.finish_reason = snap.finish_reason
        if snap.done_time is not None:
            r.done_time = snap.done_time
