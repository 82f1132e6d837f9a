"""FCFS continuous-batching scheduler: queue -> slots, EOS/budget -> free.

The policy layer between the request queue and the KV-cache pool. FCFS
(first-come-first-served) admission is the serving baseline — no reordering,
no preemption — which keeps TTFT fairness trivial to reason about and makes
the scheduler invariants sharp enough to pin in tests:

- a request is admitted the first tick the POOL accepts it
  (``pool.can_admit``: a free slot AND the block budget after prefix
  sharing), never before a
  request that arrived earlier (queue order IS arrival order — the
  head-of-line request is probed, so a big request is never starved by
  smaller ones slipping past it);
- admission BINDS the sequence to its slot inside the loop
  (``pool.bind_seq``: the pool matches/references shared prefix
  blocks and reserves the worst-case budget), so a burst cannot admit
  past the pool's actual capacity;
- retirement (EOS sampled, or ``max_new_tokens`` reached) unbinds and
  releases in the SAME tick, so a waiting request boards on the very next
  tick — that mid-flight boarding is the whole point of continuous
  batching;
- the pool's own guards make double-occupancy, double-release and block
  double-alloc/free raise rather than corrupt (``serve/slots.py``).

Smarter policies subclass and override :meth:`FCFSScheduler.pick`:
:class:`PriorityScheduler` (the scenario suite's policy) admits by request
``priority`` — FCFS within a class — and, when the pool cannot fit a
higher-priority request, *preempts* best-effort traffic: a lower-priority
active request is evicted (slot and blocks freed, request re-queued with
its emitted tokens intact) so the interactive request's prefill boards this
tick instead of waiting out a batch request's whole decode. The preempted
request later re-admits and recomputes its K/V from ``resume_seq`` without
touching its key stream, so its final tokens are bit-exact vs never having
been preempted (tests/test_scenarios.py).
"""

from __future__ import annotations

import collections

from simple_distributed_machine_learning_tpu.serve.request import (
    ACTIVE,
    DONE,
    QUEUED,
    Request,
)
from simple_distributed_machine_learning_tpu.serve.slots import PagedKVPool


class FCFSScheduler:
    """First-come-first-served admission over a :class:`PagedKVPool`."""

    def __init__(self, pool: PagedKVPool) -> None:
        self.pool = pool
        self.queue: collections.deque[Request] = collections.deque()
        # the engine this scheduler serves (attach()): policies that evict
        # active requests (PriorityScheduler) need it; FCFS never does
        self._engine = None
        self._board_count = 0

    def attach(self, engine) -> None:
        """Called by the engine at construction; see ``_engine``."""
        self._engine = engine

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    def enqueue(self, request: Request) -> None:
        if request.state != QUEUED:
            raise ValueError(
                f"request {request.rid} is {request.state}, not queued")
        self.queue.append(request)

    def pick(self) -> Request:
        """The next request to admit (FCFS: the oldest). Override for other
        policies; callers guarantee the queue is non-empty."""
        return self.queue.popleft()

    def admit(self) -> list[Request]:
        """Board waiting requests into free slots (as many as fit), FCFS.
        Returns the newly admitted requests with ``slot`` assigned; the
        engine prefills each one.

        Admission is gated on the POOL's judgment (``pool.can_admit``), not
        just a free slot: "a slot is free AND enough blocks remain for
        this request's worst-case footprint after prefix sharing". The
        gate runs on the request :meth:`pick` actually
        RETURNS (not a peeked head), so a subclass policy reordering the
        queue is still budget-checked; a picked request that doesn't fit
        goes back to the front and admission stops — head-of-line blocking,
        no starvation of big requests behind a stream of small ones."""
        admitted = []
        while self.queue:
            r = self.pick()
            if not self.pool.can_admit(r) and not self._make_room(r):
                self.queue.appendleft(r)
                break
            # the adapter gate runs AFTER the pool accepts (a free slot is
            # what makes a free bank row structurally certain) and BEFORE
            # the slot binds: it pins/uploads the request's adapter-bank
            # row at this tick boundary (serve/adapters.py)
            gate = getattr(self._engine, "_adapter_board", None)
            if gate is not None and not gate(r):
                self.queue.appendleft(r)
                break
            r.slot = self.pool.acquire(r.rid)
            # bind INSIDE the loop: the paged pool reserves this request's
            # block budget here, so the next iteration's can_admit probe
            # already sees it (a burst cannot over-admit the pool)
            r.prefill_pos = self.pool.bind_seq(r)
            r.state = ACTIVE
            r._board_seq = self._board_count
            self._board_count += 1
            admitted.append(r)
        return admitted

    def _make_room(self, request: Request) -> bool:
        """Policy hook: may the scheduler free capacity for ``request``
        (e.g. by preempting lower-priority actives)? FCFS never reorders or
        evicts — a blocked head blocks."""
        return False

    def retire(self, request: Request, reason: str) -> None:
        """Free the request's slot immediately (same tick) so the next
        :meth:`admit` can reuse it."""
        if request.state != ACTIVE or request.slot is None:
            raise ValueError(
                f"request {request.rid} is not active (state "
                f"{request.state!r}, slot {request.slot!r})")
        self.pool.unbind_seq(request.slot)
        self.pool.release(request.slot)
        request.slot = None
        request.state = DONE
        request.finish_reason = reason


class PriorityScheduler(FCFSScheduler):
    """Priority-class admission with prefill preemption of best-effort
    traffic (the scenario suite's policy; ``resilience/scenarios.py``).

    - :meth:`pick` returns the highest-``priority`` queued request, FCFS
      within a priority (queue position is arrival order, so the scan's
      first maximum is the oldest of its class);
    - when the pool cannot admit the pick, :meth:`_make_room` preempts
      ACTIVE requests of strictly lower priority — lowest priority first,
      newest-boarded first within a priority (the least sunk work) — until
      the pick fits or no eligible victim remains. Victims are re-queued at
      the FRONT (they arrived before anything still waiting of their class)
      and later resume by recomputing K/V for their emitted tokens, key
      stream untouched — output-preserving preempt-and-recompute, so SLO
      protection is a scheduling change, not a correctness change;
    - the base class's budget gate still runs on whatever pick returns, so
      admission can never outspend the pool.
    """

    def pick(self) -> Request:
        best_i = 0
        for i, r in enumerate(self.queue):
            if r.priority > self.queue[best_i].priority:
                best_i = i
        r = self.queue[best_i]
        del self.queue[best_i]
        return r

    def _victims_below(self, priority: int) -> list[Request]:
        victims = [self._engine.requests[self.pool.occupant(s)]
                   for s in self.pool.active_slots()]
        return [v for v in victims if v.priority < priority]

    def _make_room(self, request: Request) -> bool:
        if self._engine is None:
            return False
        if self.pool.prefetch_blocked(request):
            # an in-flight host->HBM upload covers this request's prefix:
            # the ONE can_admit failure eviction can never fix — it boards
            # when the upload lands, so preempting would destroy work for
            # nothing (serve/slots.py host offload tier)
            return False
        victims = self._victims_below(request.priority)
        if not victims:
            return False
        # feasibility precheck: eviction discards the victims' computed K/V
        # irreversibly, so never start unless freeing EVERY eligible victim
        # would cover the requester's block shortfall — otherwise the loop
        # would strand the requester unadmitted after throwing away work
        # (the slot side needs no precheck: any one eviction frees a slot)
        if self.pool.admit_shortfall(request) > sum(
                self.pool.freeable_blocks(v.slot) for v in victims):
            return False
        while not self.pool.can_admit(request):
            victims = self._victims_below(request.priority)
            if not victims:         # pragma: no cover - precheck bound
                return False
            # lowest priority first; newest boarding within it
            victim = max(victims,
                         key=lambda v: (-v.priority, v._board_seq))
            self._engine.preempt(victim.rid)
        return True
