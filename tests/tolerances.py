"""Dtype-aware comparison tolerances shared across the attention tests.

One rule for every test that compares attention/decode outputs whose K/V
round-tripped a storage dtype (flash kernel vs dense, bf16 caches vs f32,
quantized paged blocks vs wide): the tolerance is a property of the
STORAGE dtype, not of the individual test. Pinning it here ends the
per-test magic-number drift that left one bf16 comparison strict enough
to flake on backends whose accumulation order differs (the PR-15
known-env failure: bf16 beam decode flipping a near-tie ordering).

``attn_tol`` lives in the package (``utils/tolerances.py``) so that
``chip_smoke.py`` holds the chip to the same pins; it is re-exported here.
"""

from simple_distributed_machine_learning_tpu.utils.tolerances import (  # noqa: F401
    attn_tol,
)


def near_tie_token_mismatch_budget() -> float:
    """Fraction of tokens a sub-f32 cache may legitimately flip in an
    ARGMAX-over-near-ties decode (beam ordering, sampled top-k edges)
    before the comparison counts as a real divergence. Token streams with
    genuine math bugs diverge completely within a few positions; rounding
    flips stay sparse."""
    return 0.25
