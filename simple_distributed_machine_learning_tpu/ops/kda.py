"""Pallas gated delta rule: the recurrence of a KDA (Kimi Delta Attention)
layer with its matrix state in VMEM.

Per sequence ``n``, head ``h`` and token ``t``, with the state ``S [dk, dv]``
float32 (key lanes down, value lanes across)::

    S' = diag(alpha_t) S_{t-1}                       alpha_t = exp(g_t) in (0, 1)^dk
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(the same as ``S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t
k_t v_t^T``): the state is decayed by a vector a token, corrected by what it
already predicts for the key, and read by the query. Neither kernel of
``ops/selective_scan.py`` computes it: their state is ``[states, channels]``
under a diagonal decay, with nothing that reads the state back into its own
update.

One kernel body (``name="kda_recurrence"``) for both serving shapes, as
``ops/selective_scan.py`` has one for its two. A STEP (the decode tick:
``N`` sequences, one token each): a grid cell is one sequence's block of
heads, its ``[heads, dk, dv]`` of state in and out once (2 MiB at 32 heads of
128 x 128: the cell moves 4 MiB, so the step overhead is nothing beside it);
a sequence whose ``live`` is 0 copies its block through and computes nothing,
so its state comes back bit for bit. A WALK (the prefill chunk: one sequence,
``L`` tokens): a grid cell is a block of heads and 32 tokens, the state block
stays in VMEM over the token axis and crosses HBM once in and once out
whatever the length. The state goes in and comes out through one aliased
buffer.

What the update needs as COLUMNS (``alpha``, ``k``, ``q`` and ``beta k``,
each ``[dk]`` down the sublanes so that it spreads across the value lanes)
arrives as rows, 32 units of the four kinds to a ``[128, dk]`` tile, and is
turned once a tile inside the kernel (one 128 x 128 transpose at ``dk`` 128):
a step's 32 units are the heads of its sequence, a walk's 32 consecutive
tokens of one head. A column laid out in HBM (one value a sublane row, the
lanes padded from 1 to 128) would be as many bytes as the state itself.
``v`` and ``o`` are rows as they come.

The walk takes its tokens ONE BY ONE (the vector unit bounds it); the blocked
form (a block of tokens at a time as matrix products) is not built
(``PERF.md``, Open questions). On backends other than a TPU the kernel runs
in Pallas interpret mode (``flash_attention._interpret``); its plain
``lax.scan`` twin lives in ``tests/test_kda.py`` and in
``bench_cells/reference/kimi_linear.py``, not in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from simple_distributed_machine_learning_tpu.ops.flash_attention import (
    _interpret,
    _struct,
    _vma_of,
    pltpu,
)

#: units (a step's heads, a walk's tokens) whose four kinds of column share
#: one tile: 4 x 32 rows turn into the 128 lanes of ``[dk, 128]``
_PACK = 32
#: output rows stored together (the float32 sublane quantum)
_ROWS = 8
#: the most bytes of state one grid cell holds
_STATE_BLOCK_BYTES = 2 * 2 ** 20


def _token(s, cols, unit: int, v_row):
    """One token of one head: ``s [dk, dv]`` and the unit's four columns of
    ``cols [dk, 4 * _PACK]`` (``alpha``, ``k``, ``q``, ``beta k`` at lanes
    ``unit``, ``_PACK + unit``, ...) with ``v_row [1, dv]`` -> ``(s, o [1,
    dv])``."""
    col = lambda kind: cols[:, kind * _PACK + unit:  # noqa: E731
                            kind * _PACK + unit + 1]
    s = s * col(0)
    err = v_row - jnp.sum(s * col(1), axis=0, keepdims=True)
    s = s + col(3) * err
    return s, jnp.sum(s * col(2), axis=0, keepdims=True)


def _columns(x):
    """``x [4, _PACK, dk]`` (the four kinds' rows) -> ``[dk, 4 * _PACK]``."""
    return x.reshape(4 * _PACK, x.shape[-1]).T


def _step_kernel(live_ref, x_ref, v_ref, h0_ref, o_ref, h_ref, *, hb: int):
    """One (sequence, head block) cell of a step. ``live_ref``: ``[N]``
    int32 in SMEM; ``x_ref``: ``[1, 1, 4, _PACK, dk]``, unit ``i`` the
    block's head ``i``; ``v_ref`` / ``o_ref``: ``[1, hb, dv]``; ``h0_ref`` /
    ``h_ref``: ``[1, hb, dk, dv]``."""
    live = live_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _advance():
        cols = _columns(x_ref[0, 0])
        for h in range(hb):
            h_ref[0, h], o_ref[0, h:h + 1, :] = _token(
                h0_ref[0, h], cols, h, v_ref[0, h:h + 1, :])

    @pl.when(jnp.logical_not(live))
    def _sit_out():
        h_ref[...] = h0_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _walk_kernel(x_ref, v_ref, h0_ref, o_ref, h_ref, *, hb: int):
    """One (head block, 32 tokens) cell of a walk. ``x_ref``: ``[hb, 1, 4,
    _PACK, dk]``, unit ``i`` the cell's token ``i``; ``v_ref`` / ``o_ref``:
    ``[hb, _PACK, dv]``; ``h0_ref`` / ``h_ref``: ``[1, hb, dk, dv]``, the
    latter kept from one token block to the next."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        h_ref[...] = h0_ref[...]

    def head(h, _):
        cols = _columns(x_ref[h, 0])
        s = h_ref[0, h]
        for t0 in range(0, _PACK, _ROWS):
            rows = v_ref[h, t0:t0 + _ROWS, :]
            outs = []
            for t in range(_ROWS):
                s, o = _token(s, cols, t0 + t, rows[t:t + 1])
                outs.append(o)
            o_ref[h, t0:t0 + _ROWS, :] = jnp.concatenate(outs, axis=0)
        h_ref[0, h] = s

    lax.fori_loop(0, hb, head, None)


def _tiles(g, k, q, bk, units: int):
    """The four kinds ``[..., units, dk]`` as tiles ``[..., ceil(units /
    _PACK), 4, _PACK, dk]``: ``alpha = exp(g)``, ``k``, ``q``, ``beta k``;
    the units past the real ones decay by 1 and add nothing."""
    pad = -units % _PACK
    kinds = []
    for i, a in enumerate((g, k, q, bk)):
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, 0)])
        a = jnp.exp(a) if i == 0 else a
        kinds.append(a.reshape(*a.shape[:-2], -1, _PACK, a.shape[-1]))
    return jnp.stack(kinds, axis=-3)


def _call(kernel, grid, in_specs, out_specs, out_shape, state_at: int,
          semantics, args):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, input_output_aliases={state_at: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=8 * _STATE_BLOCK_BYTES + 16 * 2 ** 20),
        interpret=_interpret(), name="kda_recurrence")(*args)


def kda_recurrence(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, state: jax.Array,
                   live: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """``(o [N, L, H, dv], state [N, H, dk, dv])`` of the recurrence above,
    float32.

    ``q`` / ``k`` / ``g``: ``[N, L, H, dk]`` (the query already scaled, ``g``
    the LOG of the decay, ``<= 0``); ``v``: ``[N, L, H, dv]``; ``beta``:
    ``[N, L, H]``; ``state``: ``[N, H, dk, dv]`` float32, the state before
    the first token (donate it: the result's state is written over it).
    ``live [N]`` (a step, ``L = 1``, alone): the sequences that advance; the
    others' state comes back bit for bit and their ``o`` is zero. ``H`` is at
    most 32 or a multiple of 32. Several sequences of several tokens are
    walked one sequence after the other."""
    f32 = jnp.float32
    n, n_tok, heads, dk = q.shape
    dv = v.shape[-1]
    if (k.shape != q.shape or g.shape != q.shape
            or v.shape != (n, n_tok, heads, dv)
            or beta.shape != (n, n_tok, heads)
            or state.shape != (n, heads, dk, dv)):
        raise ValueError(
            f"kda_recurrence: q {q.shape} wants k and g alike, v [{n}, "
            f"{n_tok}, {heads}, dv], beta [{n}, {n_tok}, {heads}] and state "
            f"[{n}, {heads}, {dk}, dv]; got k {k.shape}, g {g.shape}, v "
            f"{v.shape}, beta {beta.shape}, state {state.shape}")
    if heads > _PACK and heads % _PACK:
        raise ValueError(f"kda_recurrence: {heads} heads are neither at most "
                         f"{_PACK} nor a multiple of it")
    if live is not None and n_tok != 1:
        raise ValueError("kda_recurrence: live belongs to a step of one "
                         "token a sequence")
    q, k, v, g, state = (a.astype(f32) for a in (q, k, v, g, state))
    bk = beta.astype(f32)[..., None] * k
    # the heads of one cell: all of them up to 32, and no more state than
    # the budget (a divisor of the heads, so that no block is ragged)
    hb = next(h for h in range(min(heads, _PACK), 0, -1)
              if heads % h == 0 and (h == 1 or h * dk * dv * 4
                                     <= _STATE_BLOCK_BYTES))
    if n_tok == 1:
        return _step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], bk[:, 0], state,
                     live, hb)
    outs, states = zip(*(
        _walk(q[i], k[i], v[i], g[i], bk[i], state[i:i + 1], hb)
        for i in range(n)))
    return jnp.stack(outs), jnp.concatenate(states)


def _step(q, k, v, g, bk, state, live, hb: int):
    n, heads, dk = q.shape
    dv = v.shape[-1]
    groups = heads // hb

    def by_group(a):        # [N, H, dk] -> [N, groups, hb, dk]
        return a.reshape(n, groups, hb, dk)

    x = _tiles(*(by_group(a) for a in (g, k, q, bk)), hb)[:, :, 0]
    live = (jnp.ones((n,), jnp.int32) if live is None
            else live.astype(jnp.int32))
    vma = _vma_of(q, v, state)
    rows = pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0))
    block = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    o, state = _call(
        functools.partial(_step_kernel, hb=hb), (n, groups),
        [pl.BlockSpec(memory_space=pltpu.SMEM),
         pl.BlockSpec((1, 1, 4, _PACK, dk), lambda i, j: (i, j, 0, 0, 0)),
         rows, block],
        [rows, block],
        [_struct((n, heads, dv), jnp.float32, vma),
         _struct(state.shape, jnp.float32, vma)],
        3, ("parallel", "parallel"), (live, x, v, state))
    return o[:, None], state


def _walk(q, k, v, g, bk, state, hb: int):
    n_tok, heads, dk = q.shape
    dv = v.shape[-1]
    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    x = _tiles(*(by_head(a) for a in (g, k, q, bk)), n_tok)
    tiles = x.shape[1]
    v = jnp.pad(by_head(v), ((0, 0), (0, tiles * _PACK - n_tok), (0, 0)))
    vma = _vma_of(q, v, state)
    rows = pl.BlockSpec((hb, _PACK, dv), lambda i, j: (i, j, 0))
    block = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (0, i, 0, 0))
    o, state = _call(
        functools.partial(_walk_kernel, hb=hb), (heads // hb, tiles),
        [pl.BlockSpec((hb, 1, 4, _PACK, dk), lambda i, j: (i, j, 0, 0, 0)),
         rows, block],
        [rows, block],
        [_struct(v.shape, jnp.float32, vma),
         _struct(state.shape, jnp.float32, vma)],
        2, ("parallel", "arbitrary"), (x, v, state))
    return by_head(o[:, :n_tok]), state
