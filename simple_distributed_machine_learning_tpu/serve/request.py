"""The serving request object: prompt, sampling params, lifecycle timestamps.

One :class:`Request` is one user sequence moving through the engine:
``QUEUED`` (waiting for a slot) → ``ACTIVE`` (owns a KV-cache slot, decoding)
→ ``DONE`` (EOS emitted or ``max_new_tokens`` reached; slot freed), or →
``SHED`` (the overload/deadline exit: the supervisor cancelled it with a
structured rejection in ``finish_reason`` — ``deadline``, ``backpressure``
or ``class`` — and its slot/block budget was refunded). Sampling
config is per-request — greedy (``temperature=0``) or temperature sampling
with optional top-k / top-p filtering — with an independent key stream seeded
from ``seed``, so two requests never share randomness and each one's tokens
are bit-exact vs decoding it alone (tests/test_serve.py).

Latency accounting follows the serving-standard split: TTFT (time to first
token — queue wait + prefill) and TPOT (time per output token — the decode
tick cadence), both recorded by the engine on host wall-clock.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

QUEUED = "queued"
ACTIVE = "active"
DONE = "done"
SHED = "shed"


@dataclasses.dataclass
class Request:
    """One sequence's serving state; constructed via ``engine.submit``."""

    rid: int
    prompt: np.ndarray                  # [T0] int32 tokens
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    eos_id: int | None = None
    seed: int = 0
    # streaming: called with (request, token:int) as each token materializes
    on_token: Callable | None = None
    # traffic class: the scenario suite's per-class SLO label and the
    # priority schedulers' ordering key (higher boards first and may
    # preempt lower — serve/scheduler.py::PriorityScheduler). 0 is the
    # best-effort floor; cls=None requests aggregate into the unlabeled
    # serving metrics only.
    cls: str | None = None
    priority: int = 0
    # deadlines, in seconds RELATIVE to submit_time: ``ttft_deadline_s``
    # bounds time-to-first-token, ``deadline_s`` bounds the whole request.
    # The ENGINE only stores them; enforcement (shed at tick boundaries,
    # budget refunded) is the serve supervisor's job — an unsupervised
    # engine is the "no-deadline baseline" the overload scenarios compare
    # against (serve/supervisor.py).
    ttft_deadline_s: float | None = None
    deadline_s: float | None = None
    # multi-tenant serving: the named LoRA adapter this request decodes
    # under (serve/adapters.py AdapterStore), None = the base model. Part
    # of the request's IDENTITY — journaled (`adp`), carried across
    # recovery/migration, and the prefix-cache namespace key, because K/V
    # computed under one adapter is wrong for every other.
    adapter: str | None = None
    # generation by diffusion over blocks (a model whose
    # ``PagedServing.block`` is > 1; set by the engine): the block's
    # length and this request's denoising steps (1..block)
    block: int = 1
    denoising_steps: int = 0

    # -- lifecycle (engine-owned) -----------------------------------------
    state: str = QUEUED
    slot: int | None = None
    # chunked prefill: next prompt position to compute while the request
    # is admitted but not yet decoding (None once seated)
    prefill_pos: int | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    # ``block > 1``: every committed block as it was served, ``(start
    # position, its tokens, the forward that fixed each: 0 for the prompt's
    # remainder)`` — what rebuilds each denoising forward's input
    blocks: list[tuple] = dataclasses.field(default_factory=list)
    key_data: np.ndarray | None = None  # live PRNG key data (uint32 [2])
    # speculative decoding: the draft model's SEPARATE key stream (set by
    # the engine when a draft is configured; fold_in(key(seed), 1), so
    # sampled proposals never consume the target stream's splits)
    draft_key_data: np.ndarray | None = None
    submit_time: float | None = None
    first_token_time: float | None = None
    done_time: float | None = None
    # "eos" | "length", or the SHED reasons "deadline" | "backpressure"
    # | "class"
    finish_reason: str | None = None
    # migration cause of the last journal `snap` written for this request
    # ("failure" | "handoff"; the record's `why` key — serve/journal.py),
    # None for never-migrated requests and pre-field journals
    snap_reason: str | None = None
    # preemption accounting: a preempted request goes back to QUEUED with
    # its emitted tokens intact; re-admission recomputes its K/V from
    # `resume_seq` WITHOUT touching the key stream, so the continued decode
    # is bit-exact vs never having been preempted (tests/test_scenarios.py)
    n_preempted: int = 0
    # scheduler bookkeeping: boarding order (set at admission), used by the
    # priority scheduler's newest-first victim pick
    _board_seq: int = -1
    # the adapter-bank row this request's admission pinned (0 = base row;
    # engine-transient — NOT identity: a re-admission or another replica
    # may seat the same adapter on a different row)
    _adapter_row: int = 0
    # the resolved prefix-cache namespace (AdapterStore.namespace_of —
    # version-qualified, set by the engine at submit/restore and refreshed
    # at the admission gate); None = derive from `adapter` by name alone
    # (pools driven without an adapter store). Engine-transient.
    _prefix_ns: bytes | None = None

    @property
    def resume_seq(self) -> np.ndarray:
        """The token sequence (re-)admission must have K/V for: the prompt,
        plus — after a preemption — every emitted token except the newest
        (whose K/V the next decode step writes; it rides in ``last_token``).
        Fresh requests: exactly the prompt. With ``block > 1`` every
        emitted token belongs to a committed block and needs its K/V: the
        block in progress starts again from masks."""
        if not self.tokens:
            return self.prompt
        kept = self.tokens if self.block > 1 else self.tokens[:-1]
        return np.concatenate([self.prompt, np.asarray(kept, np.int32)])

    @property
    def resume_max_new(self) -> int:
        """Remaining new-token budget paired with :attr:`resume_seq` so the
        pool's worst-case row bound (``len(seq) + budget - 1``) stays exactly
        ``prompt_len + max_new_tokens - 1`` across preemptions."""
        if self.block > 1:
            return self.max_new_tokens - len(self.tokens)
        return self.max_new_tokens - max(0, len(self.tokens) - 1)

    @property
    def ttft_s(self) -> float | None:
        if self.submit_time is None or self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def tpot_s(self) -> float | None:
        """Mean time per output token AFTER the first (None for 1-token
        requests — there is no inter-token interval to average)."""
        if (self.first_token_time is None or self.done_time is None
                or len(self.tokens) < 2):
            return None
        return (self.done_time - self.first_token_time) / (len(self.tokens) - 1)

    def emit(self, token: int) -> None:
        self.tokens.append(int(token))
        if self.on_token is not None:
            self.on_token(self, int(token))

    def finished_by(self, token: int) -> str | None:
        """Finish reason if ``token`` (just emitted) terminates the request."""
        if self.eos_id is not None and int(token) == self.eos_id:
            return "eos"
        if len(self.tokens) >= self.max_new_tokens:
            return "length"
        return None


def validate_request(prompt: np.ndarray, max_new_tokens: int,
                     temperature: float, top_k: int | None,
                     top_p: float | None, vocab: int, max_len: int) -> None:
    """Submit-time validation: length/prompt bounds here, sampling args
    delegated to the one-shot decoders' ``check_sampling_args`` — one
    source of truth, so a request the engine accepts is exactly one
    ``make_cached_decoder`` accepts."""
    prompt = np.asarray(prompt)
    if prompt.ndim != 1 or prompt.shape[0] < 1:
        raise ValueError(
            f"prompt must be a non-empty 1-D token array, got shape "
            f"{prompt.shape}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if prompt.shape[0] + max_new_tokens > max_len:
        raise ValueError(
            f"prompt {prompt.shape[0]} + max_new_tokens {max_new_tokens} "
            f"exceeds the pool's sequence budget {max_len}")
    if prompt.min() < 0 or prompt.max() >= vocab:
        raise ValueError(
            f"prompt tokens outside [0, vocab={vocab})")
    from simple_distributed_machine_learning_tpu.models.gpt import (
        check_sampling_args,
    )
    check_sampling_args(temperature, top_k, top_p, vocab)
