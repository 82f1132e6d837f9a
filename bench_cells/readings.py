"""Arithmetic over a runner's records that more than one metric reader
uses: token gaps, percentiles, per-tick counts."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default), in plain Python."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def token_gaps_s(records: dict) -> list[float]:
    """Every gap between consecutive output tokens of every request."""
    return [b - a for r in records["requests"]
            for a, b in zip(r["stamps"], r["stamps"][1:])]


def tokens_received(records: dict) -> int:
    return sum(len(r["stamps"]) for r in records["requests"])


def ttfts_s(records: dict) -> list[float]:
    return [r["stamps"][0] - r["t_submit"] for r in records["requests"]
            if r["stamps"]]


def decode_tokens_by_tick(records: dict) -> dict[int, int]:
    """Tick index -> tokens the batched decode emitted in it (a request's
    first token comes from its prefill, not from the decode)."""
    out: dict[int, int] = {}
    for r in records["requests"]:
        for tick in r["ticks"][1:]:
            out[tick] = out.get(tick, 0) + 1
    return out


def live_positions_by_tick(records: dict) -> dict[int, int]:
    """Tick index -> K/V positions the decode attends to in it, summed over
    its decoding slots: the request's ``j``-th token (``j >= 1``, counted
    from 0) is chosen after attending ``prompt_len + j`` positions."""
    out: dict[int, int] = {}
    for r in records["requests"]:
        for j, tick in enumerate(r["ticks"]):
            if j >= 1:
                out[tick] = out.get(tick, 0) + r["prompt_len"] + j
    return out
