"""Pallas selective scan: the Mamba recurrences with their state in VMEM.

Mamba-1, per sequence ``n`` and token ``t`` (``Di`` channels, ``S`` states a
channel)::

    H_t = exp(delta_t[None, :] * A) * H_{t-1} + (delta_t * x_t)[None, :] * B_t[:, None]
    y_t = (sum_s H_t[s, :] * C_t[s] + D * x_t) * silu(z_t)

``H`` is ``[S, Di]``: the channels lie in the 128-lane dimension and the
``S = 16`` states in two sublane groups, so a block of it is whole vregs
(the transposed ``[Di, S]`` would pad 16 to 128 lanes: eight times the
bytes). The kernel keeps a block of ``H`` on chip and walks the tokens, so
the state crosses HBM once in and once out whatever the length; nothing of
size ``[L, S, Di]`` ever exists.

One kernel for both serving shapes, as ``ops/paged_attention.py`` is one
kernel for ``K = 1`` and ``K`` tokens: the prefill chunk is ``N = 1``
sequence of ``L`` tokens (a grid step walks ``L`` tokens of one sequence),
the decode tick ``N = n_slots`` sequences of ``L = 1`` token (a grid step
takes 8 sequences, so its blocks are whole ``(8, 128)`` tiles). The state
goes in and comes out through one aliased buffer.

Mamba-2 (Dao & Gu 2024) through the same entry, taken when ``b`` / ``c``
come by GROUP (``[N, L, G, S]``: the channels lie in ``G`` equal runs, and a
channel reads its own run's ``B_t`` / ``C_t``), ``a`` is one decay a channel
(``[Di]``: a head's value repeated over its channels, as ``delta`` and ``d``
are) and there is no ``z`` (the family's norm wants ``y * silu(z)`` over a
whole group, so the gate is the caller's)::

    H_t = exp(delta_t * a)[None, :] * H_{t-1} + (delta_t * x_t)[None, :] * B_t[g][:, None]
    y_t = sum_s H_t[s, :] * C_t[g][s] + D * x_t

A second kernel in this module (``name="selective_scan_grouped"``), not a
branch of the first: with ``S = 128`` a column of ``B_t`` laid out as the
first kernel takes it (one value a sublane row, the lane dimension padded
from 1 to 128) would be 128 times its bytes in HBM for each of ``G`` groups,
a quarter of the state's own traffic, so here eight rows' columns share a
tile (``[S, 8]``: eight sequences of a decode step, eight consecutive tokens
of a walk) and a row takes its column by a static lane slice; the decay is
one ``exp`` a channel, not one a state; and the inner pass is narrower, so
that ``[128, cols]`` of state stays a quarter of the register file. The
grid, the ``[S, Di]`` state, the aliased buffer and the two serving shapes
are the first kernel's.

On backends other than a TPU the kernels run in Pallas interpret mode
(``flash_attention._interpret``); their plain ``lax.scan`` twins live in
``tests/test_selective_scan.py``, not in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from simple_distributed_machine_learning_tpu.ops.flash_attention import (
    _LANES,
    _interpret,
    _struct,
    _vma_of,
    pltpu,
)

#: channels one inner pass holds in registers: ``[16, 512]`` f32 is 8 vregs
_COLS = 512
#: sequences a decode grid step takes (the f32 sublane quantum)
_ROWS = 8
#: VMEM the pipelined blocks may take before the scoped limit is raised
_VMEM_BUDGET = 12 * 2 ** 20


def _scan_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, h_ref, *, nb: int, n_tok: int, cols: int):
    """One (sequence block, channel block) grid cell.

    ``x_ref`` / ``dt_ref`` / ``z_ref`` / ``y_ref``: ``[1, n_tok * nb, bd]``,
    row ``t * nb + i`` is token ``t`` of the block's sequence ``i``;
    ``b_ref`` / ``c_ref``: ``[1, n_tok * nb, S, 1]`` (a token's ``S`` values
    as a sublane column, so it broadcasts along the lanes);
    ``a_ref``: ``[S, bd]``; ``d_ref``: ``[1, bd]``; ``h0_ref`` / ``h_ref``:
    ``[nb, S, bd]``."""
    bd = x_ref.shape[-1]
    for c0 in range(0, bd, cols):
        cs = slice(c0, c0 + cols)
        a = a_ref[:, cs]
        d = d_ref[:, cs]
        for i in range(nb):
            def token(t, h, i=i, cs=cs, a=a, d=d):
                row = t * nb + i
                at = (0, pl.ds(row, 1), cs)
                x, dt, z = x_ref[at], dt_ref[at], z_ref[at]   # [1, cols]
                h = jnp.exp(dt * a) * h + (dt * x) * b_ref[0, row]
                y = jnp.sum(h * c_ref[0, row], axis=0, keepdims=True) + d * x
                y_ref[at] = y * (z / (1.0 + jnp.exp(-z)))
                return h

            h = h0_ref[i, :, cs]
            # a decode tick's one token is a straight line, not a loop
            h_ref[i, :, cs] = (token(0, h) if n_tok == 1
                               else lax.fori_loop(0, n_tok, token, h))


def _channel_block(di: int, rows: int, nb: int, n_state: int) -> int:
    """The widest lane-multiple divisor of ``di`` whose double-buffered
    blocks (x, delta, z, y and the state in and out) fit the budget."""
    best = min(di, _LANES)
    for bd in range(_LANES, di + 1, _LANES):
        if di % bd == 0 and 8 * bd * (4 * rows + 2 * nb * n_state) \
                <= _VMEM_BUDGET * 2 // 3:
            best = bd
    return best


def selective_scan(x: jax.Array, delta: jax.Array, z: jax.Array | None,
                   b: jax.Array, c: jax.Array, a: jax.Array, d: jax.Array,
                   h0: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(y [N, L, Di], h [N, S, Di])`` of the recurrences above, float32.

    ``x`` / ``delta``: ``[N, L, Di]``; ``d``: ``[Di]``; ``h0``: ``[N, S,
    Di]``, the state before the first token (donate it: the result's state
    is written over it). Then either ``z [N, L, Di]``, ``b`` / ``c [N, L,
    S]`` and ``a [S, Di]`` (the negative ``-exp(A_log)``): the first
    recurrence, gated; or ``z`` ``None``, ``b`` / ``c [N, L, G, S]`` with
    ``G`` dividing ``Di`` into runs of a multiple of 128 channels, and ``a
    [Di]``: the second. A sequence whose ``delta`` is 0 keeps its state bit
    for bit (``exp(0) * H + 0``): how a decode tick leaves the slots that
    sit it out untouched."""
    n, n_tok, di = x.shape
    if b.ndim == 4:
        return _grouped_scan(x, delta, z, b, c, a, d, h0)
    n_state = a.shape[0]
    if (z is None or b.shape != (n, n_tok, n_state) or c.shape != b.shape
            or h0.shape != (n, n_state, di)):
        raise ValueError(
            f"selective_scan: x {x.shape} with a {a.shape} wants z "
            f"{x.shape}, b/c [{n}, {n_tok}, {n_state}] and h0 [{n}, "
            f"{n_state}, {di}] (or, by group: no z, b/c [{n}, {n_tok}, G, "
            f"S], a [{di}], h0 [{n}, S, {di}]); got z "
            f"{None if z is None else z.shape}, b {b.shape}, c {c.shape}, "
            f"h0 {h0.shape}")
    # a step over many sequences takes them 8 at a time; a walk over many
    # tokens takes one sequence
    nb = next(k for k in range(_ROWS, 0, -1) if n % k == 0) \
        if n_tok == 1 else 1
    g, rows = n // nb, n_tok * nb
    f32 = jnp.float32

    def rows_of(v):                       # [N, L, ...] -> [G, L * nb, ...]
        v = v.astype(f32).reshape(g, nb, n_tok, *v.shape[2:])
        return jnp.swapaxes(v, 1, 2).reshape(g, rows, *v.shape[3:])

    bd = _channel_block(di, rows, nb, n_state)
    cols = _COLS if bd % _COLS == 0 else bd
    wide = pl.BlockSpec((1, rows, bd), lambda i, j: (i, 0, j))
    column = pl.BlockSpec((1, rows, n_state, 1), lambda i, j: (i, 0, 0, 0))
    state = pl.BlockSpec((nb, n_state, bd), lambda i, j: (i, 0, j))
    # the b/c columns pad their one lane to 128: count them as laid out
    vmem = (8 * bd * (4 * rows + 2 * nb * n_state)
            + 4 * rows * n_state * _LANES * 4)
    params = dict(dimension_semantics=("parallel", "parallel"))
    if vmem > _VMEM_BUDGET:
        params["vmem_limit_bytes"] = min(2 * vmem, 96 * 2 ** 20)
    vma = _vma_of(x, delta, z, h0)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, nb=nb, n_tok=n_tok, cols=cols),
        grid=(g, di // bd),
        in_specs=[wide, wide, wide, column, column,
                  pl.BlockSpec((n_state, bd), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bd), lambda i, j: (0, j)),
                  state],
        out_specs=[wide, state],
        out_shape=[_struct((g, rows, di), f32, vma),
                   _struct((n, n_state, di), f32, vma)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(**params),
        interpret=_interpret(),
        name="selective_scan",
    )(rows_of(x), rows_of(delta), rows_of(z), rows_of(b)[..., None],
      rows_of(c)[..., None], a.astype(f32), d.astype(f32)[None],
      h0.astype(f32))
    y = jnp.swapaxes(y.reshape(g, n_tok, nb, di), 1, 2)
    return y.reshape(n, n_tok, di), h


# -- by group ------------------------------------------------------------------

#: rows (sequences of a step, tokens of a walk) whose columns share a tile
_PACK = 8
#: channels one inner pass of the grouped kernel takes: ``[128, 128]`` f32
#: of state is 16 vregs
_GROUPED_COLS = 128
#: the most bytes of state one grid step of the grouped kernel holds
_GROUPED_STATE_BYTES = 2 * 2 ** 20


def _grouped_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                    y_ref, h_ref, *, nb: int, n_tok: int, cols: int):
    """One (sequence block, channel block) grid cell of the second
    recurrence.

    ``x_ref`` / ``dt_ref`` / ``y_ref``: ``[1, rows, bd]``, row ``t * nb +
    i`` is token ``t`` of the block's sequence ``i`` (``rows`` a multiple of
    8; the rows past the real ones have ``dt = 0``); ``b_ref`` / ``c_ref``:
    ``[1, rows / 8, S, 8]`` of the channel block's own group, row ``r``'s
    ``S`` values the lane ``r % 8`` of tile ``r // 8``; ``a_ref`` /
    ``d_ref``: ``[1, bd]``; ``h0_ref`` / ``h_ref``: ``[nb, S, bd]``. Either
    ``n_tok == 1`` (a step: ``nb <= 8`` sequences, one tile) or ``nb == 1``
    (a walk)."""
    bd = x_ref.shape[-1]
    for c0 in range(0, bd, cols):
        cs = slice(c0, c0 + cols)
        a = a_ref[:, cs]
        d = d_ref[:, cs]

        def tile(t, hs, cs=cs, a=a, d=d):
            """Rows ``8 t .. 8 t + 7`` from the states ``hs``: row ``k``
            advances ``hs[k % len(hs)]`` (a step's row its own sequence's,
            a walk's rows the one sequence's in turn)."""
            at = (0, pl.ds(pl.multiple_of(t * _PACK, _PACK), _PACK), cs)
            xs, dts = x_ref[at], dt_ref[at]                   # [8, cols]
            bt, ct = b_ref[0, t], c_ref[0, t]                 # [S, 8]
            hs, ys = list(hs), []
            for k in range(_PACK):
                i = k % len(hs)
                x, dt = xs[k:k + 1], dts[k:k + 1]
                if k < nb * min(n_tok, _PACK):
                    hs[i] = (jnp.exp(dt * a) * hs[i]
                             + (dt * x) * bt[:, k:k + 1])
                    ys.append(jnp.sum(hs[i] * ct[:, k:k + 1], axis=0,
                                      keepdims=True) + d * x)
                else:           # a step of fewer than 8 sequences
                    ys.append(x)
            y_ref[at] = jnp.concatenate(ys, axis=0)
            return tuple(hs)

        if n_tok == 1:
            hs = tile(0, [h0_ref[i, :, cs] for i in range(nb)])
            for i in range(nb):
                h_ref[i, :, cs] = hs[i]
        else:
            h_ref[0, :, cs], = lax.fori_loop(
                0, x_ref.shape[1] // _PACK, tile, (h0_ref[0, :, cs],))


def _grouped_scan(x, delta, z, b, c, a, d, h0):
    n, n_tok, di = x.shape
    n_groups, n_state = b.shape[2:]
    width = di // max(n_groups, 1)
    if (z is not None or b.shape != (n, n_tok, n_groups, n_state)
            or c.shape != b.shape or a.shape != (di,)
            or h0.shape != (n, n_state, di) or width * n_groups != di
            or width % _LANES):
        raise ValueError(
            f"selective_scan by group: x {x.shape} with b {b.shape} wants "
            f"no z, c {b.shape}, a [{di}], h0 [{n}, {n_state}, {di}] and "
            f"{n_groups} runs of a multiple of {_LANES} channels; got z "
            f"{None if z is None else z.shape}, c {c.shape}, a {a.shape}, "
            f"h0 {h0.shape}")
    nb = next(k for k in range(_ROWS, 0, -1) if n % k == 0) \
        if n_tok == 1 else 1
    g, rows = n // nb, n_tok * nb
    padded = -(-rows // _PACK) * _PACK
    f32 = jnp.float32

    def rows_of(v):             # [N, L, ...] -> [G, padded, ...], zeros past
        v = v.astype(f32).reshape(g, nb, n_tok, *v.shape[2:])
        v = jnp.swapaxes(v, 1, 2).reshape(g, rows, *v.shape[3:])
        return jnp.pad(v, ((0, 0), (0, padded - rows))
                       + ((0, 0),) * (v.ndim - 2))

    def columns_of(v):          # [N, L, Gr, S] -> [G, padded / 8, Gr, S, 8]
        v = rows_of(v).reshape(g, padded // _PACK, _PACK, n_groups, n_state)
        return jnp.moveaxis(v, 2, 4)

    # the widest lane-multiple divisor of a group's run whose state block
    # keeps to its budget
    bd = max(k for k in range(_LANES, width + 1, _LANES)
             if width % k == 0 and (k == _LANES or nb * n_state * k * 4
                                    <= _GROUPED_STATE_BYTES))
    cols = min(_GROUPED_COLS, bd)
    wide = pl.BlockSpec((1, padded, bd), lambda i, j: (i, 0, j))
    column = pl.BlockSpec(
        (1, padded // _PACK, None, n_state, _PACK),
        lambda i, j: (i, 0, j * bd // width, 0, 0))
    lane = pl.BlockSpec((1, bd), lambda i, j: (0, j))
    state = pl.BlockSpec((nb, n_state, bd), lambda i, j: (i, 0, j))
    # double-buffered: x, delta, y; the state in and out; the two column
    # blocks with their 8 lanes padded to 128
    vmem = 8 * (3 * padded * bd + 2 * nb * n_state * bd
                + 2 * (padded // _PACK) * n_state * _LANES)
    vma = _vma_of(x, delta, h0)
    y, h = pl.pallas_call(
        functools.partial(_grouped_kernel, nb=nb, n_tok=n_tok, cols=cols),
        grid=(g, di // bd),
        in_specs=[wide, wide, column, column, lane, lane, state],
        out_specs=[wide, state],
        out_shape=[_struct((g, padded, di), f32, vma),
                   _struct((n, n_state, di), f32, vma)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(max(2 * vmem, 16 * 2 ** 20), 96 * 2 ** 20)),
        interpret=_interpret(),
        name="selective_scan_grouped",
    )(rows_of(x), rows_of(delta), columns_of(b), columns_of(c),
      a.astype(f32)[None], d.astype(f32)[None], h0.astype(f32))
    y = jnp.swapaxes(y[:, :rows].reshape(g, n_tok, nb, di), 1, 2)
    return y.reshape(n, n_tok, di), h
