"""Static verification of Pallas kernels — the ``kernel-*`` rule family.

The preflight gate (rules.py, bounds.py) historically treated every
``pallas_call`` as an opaque primitive: the repo's strongest correctness
tool was blind exactly where its riskiest code lives (the fused
paged-attention / flash-decode kernels the serve registry runs every
tick). This module opens the box. For each ``pallas_call`` equation the
:class:`~.bounds.BoundsWalker` encounters, four machine checks run over
the kernel's OWN metadata — grid, BlockSpec index maps, block shapes,
scratch avals — so the disciplines ``ops/paged_attention.py`` argues in
comments become proofs:

- **index-map bounds** (``kernel-oob.index-map`` ERROR /
  ``kernel-unproven.index-map`` WARNING): every BlockSpec index map is
  evaluated over the interval lattice with each grid axis seeded
  ``[0, grid[i]-1]`` and scalar-prefetch operands seeded from the caller's
  declared ``spec(...)`` contracts (block-table entries <= n_blocks,
  positions < max_len). A block index that can escape
  ``[0, ceil(dim/block)-1]`` is an out-of-bounds HBM window — the
  trash-block-0 and fetch-elision-clamp disciplines, machine-checked.
  An operand handed over unblocked (``memory_space=pl.ANY``) has no index
  map: the kernel copies from it itself, and the body is walked with the
  same seeds, every ``dma_start`` that reads such an operand held to the
  operand's shape (``kernel-oob.dma-source`` ERROR /
  ``kernel-unproven.dma-source`` WARNING, which names the kernel).
- **grid write races** (``kernel-race.parallel-overwrite`` ERROR /
  ``kernel-race.unproven-map`` WARNING): each output element must be
  written by at most one cell of every ``parallel`` grid axis. The output
  index map is evaluated affinely in the grid axes: a component with a
  nonzero integer coefficient in axis ``g`` is injective along ``g``; an
  axis no component reaches means every iteration rewrites the same
  window — exactly the property an autotuner mutation silently breaks.
  ``arbitrary`` axes are sequential and may legally revisit a window
  (the online-softmax accumulate discipline).
- **tiling lint** (``kernel-tile.pad-waste`` WARNING): Mosaic pads each
  block's trailing two dims up to the dtype's minimum tile
  (f32 ``(8,128)``, bf16 ``(16,128)``, int8/fp8 ``(32,128)``); a block
  whose natural layout pads >= 4x while the transposed layout would pad
  less than half that is the known small-head-dim hazard (dh in the lane
  slot) — fix the layout, don't eat the copy. An unblocked operand is
  judged by one row of its leading axis as the caller holds it: there the
  padding is the caller's own (``ops/paged_attention.py::_whole_lanes``),
  a copy of the pool every call.
- **dtype lint** (``kernel-dtype-drift.low-precision-scratch`` WARNING):
  sub-f32 floating scratch in a kernel that carries state across grid
  iterations loses the online-softmax accumulation precision the dense
  path's f32 einsum promotion guarantees.

:func:`kernel_hbm_costs` additionally derives HBM traffic rows from the
kernels themselves (block bytes x the grid trips each index map actually
depends on), tagged ``kernel.kv_stream`` for table-indexed streams and
``kernel.io`` for the rest; what a kernel copies itself from unblocked
operands is the call's declared ``cost_estimate.bytes_accessed`` less the
blocks the walker can see. ``programs.lint_serve`` reconciles the
kv_stream bytes against the hand-built ``HBMCost`` tick model
(``decode.kv_gather`` et al.) EXACTLY — the analyzer's claim that the
fused kernel deletes the 2x ``kv_attn_reread`` pass is computed from the
kernel's own BlockSpecs, not hand-asserted.

Everything here is metadata-only: no kernel body is executed, no TPU is
required, and the checks run identically on the CPU interpret-mode traces
the test suite uses.
"""

from __future__ import annotations

import math

import numpy as np

from simple_distributed_machine_learning_tpu.analysis.report import (
    Finding,
    HBMCost,
    Severity,
)
from simple_distributed_machine_learning_tpu.analysis.trace import (
    is_low_precision,
    source_line,
    subjaxprs,
)

_INF = math.inf
_LANE = 128

#: the rule families this module emits — CLI gates and CI drills key off it
KERNEL_FAMILIES = ("kernel-oob", "kernel-unproven", "kernel-race",
                   "kernel-tile", "kernel-dtype-drift", "kernel-hbm")

#: tile-lint thresholds: flag when natural-layout padding wastes >= 4x the
#: block's bytes AND the transposed layout would waste less than half that
_WASTE_FLAG = 4.0
_WASTE_RATIO = 2.0


# -- pallas_call metadata accessors ---------------------------------------

def _grid_mapping(eqn):
    return eqn.params.get("grid_mapping")


def _grid(gm) -> tuple[int, ...]:
    out = []
    for g in getattr(gm, "grid", ()) or ():
        try:
            out.append(int(g))
        except (TypeError, ValueError):
            out.append(1)       # dynamic grid dim: treat as unit (rare)
    return tuple(out)


def _dimension_semantics(eqn, n_axes: int) -> tuple[str, ...]:
    # a mapping {"mosaic_tpu": pltpu.CompilerParams(...)}
    mosaic = dict(eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    sem = getattr(mosaic, "dimension_semantics", None)
    if not sem:
        return ("arbitrary",) * n_axes
    sem = tuple(str(s) for s in sem)
    return sem + ("arbitrary",) * (n_axes - len(sem))


def _counts(eqn, gm) -> tuple[int, int, int, int]:
    """(num_scalar_prefetch, num_inputs, num_outputs, num_scratch)."""
    n_sp = int(getattr(gm, "num_index_operands", 0) or 0)
    n_out = len(eqn.outvars)
    n_out = int(getattr(gm, "num_outputs", n_out) or n_out)
    bms = list(getattr(gm, "block_mappings", ()) or ())
    n_in = int(getattr(gm, "num_inputs", len(bms) - n_out)
               or (len(bms) - n_out))
    n_scr = int(getattr(gm, "num_scratch_operands", 0) or 0)
    return n_sp, n_in, n_out, n_scr


def _bm_parts(bm):
    """(block_shape, array_shape, dtype) of one BlockMapping, or None."""
    if bm is None:
        return None
    raw = getattr(bm, "block_shape", None)
    asd = getattr(bm, "array_aval", None)
    if raw is None or asd is None:
        return None
    shape = tuple(int(s) for s in asd.shape)
    # entries are pallas block dims: Blocked/Element carry ``block_size``;
    # Squeezed (a mapped/None dim) carries the dim whole, one row at a time
    block = [int(getattr(b, "block_size", 1)) for b in raw]
    return tuple(block), shape, np.dtype(asd.dtype)


def _index_map_jaxpr(bm):
    return getattr(bm, "index_map_jaxpr", None)


def _unblocked(aval) -> bool:
    """A ref the kernel reads by copies of its own (``pl.ANY`` / HBM):
    Pallas pipelines no block of it."""
    return str(getattr(aval, "memory_space", None)) in ("any", "hbm")


def _kernel_copies(bm) -> bool:
    return _unblocked(getattr(bm, "transformed_block_aval", None))


def _kernel_name(eqn) -> str:
    return str(eqn.params.get("name") or "pallas_call")


# -- index-map evaluation over the interval lattice ------------------------

def _eval_index_map(walker, closed, grid, sp_ivs):
    """Interval of each index-map output component, grid axes seeded
    ``[0, grid[i]-1]`` and scalar-prefetch refs seeded from the enclosing
    contract intervals."""
    from simple_distributed_machine_learning_tpu.analysis.bounds import (
        TOP,
        Interval,
    )
    jaxpr = getattr(closed, "jaxpr", closed)
    ivs = [Interval(0, max(0, g - 1)) for g in grid]
    ivs += list(sp_ivs)
    ivs = ivs[:len(jaxpr.invars)]
    ivs += [TOP] * (len(jaxpr.invars) - len(ivs))
    env = walker._sub_env(closed, ivs)
    walker._mute += 1           # inner gathers report as kernel-oob, not
    try:                        # scatter-bounds
        walker._walk(jaxpr, env)
    finally:
        walker._mute -= 1
    return [env.read(v) for v in jaxpr.outvars]


def _dep_axes(closed, n_grid: int):
    """Per-component set of grid axes each index-map output depends on
    (transitively; SMEM ``get``s propagate their index deps)."""
    jaxpr = getattr(closed, "jaxpr", closed)
    deps: dict[int, frozenset] = {}
    for i, v in enumerate(jaxpr.invars):
        deps[id(v)] = frozenset([i]) if i < n_grid else frozenset()

    def rd(atom):
        if hasattr(atom, "val"):
            return frozenset()
        return deps.get(id(atom), frozenset())

    for eqn in jaxpr.eqns:
        u = frozenset()
        for v in eqn.invars:
            u |= rd(v)
        for ov in eqn.outvars:
            deps[id(ov)] = u
    return [rd(v) for v in jaxpr.outvars]


def _affine_components(closed, n_grid: int):
    """Affine form ``(const, {axis: coef})`` of each output component, or
    ``None`` where the map is not affine in the grid axes (``get``, ``min``
    clamps, ...). A nonzero integer coefficient proves injectivity along
    that axis — the write-race certificate."""
    jaxpr = getattr(closed, "jaxpr", closed)
    aff: dict[int, tuple | None] = {}
    for i, v in enumerate(jaxpr.invars):
        aff[id(v)] = (0.0, {i: 1.0}) if i < n_grid else None

    def rd(atom):
        if hasattr(atom, "val"):
            try:
                arr = np.asarray(atom.val)
                if arr.size == 1:
                    return (float(arr.reshape(())), {})
            except (TypeError, ValueError):
                pass
            return None
        return aff.get(id(atom))

    def comb(x, y, sy):
        if x is None or y is None:
            return None
        c = x[0] + sy * y[0]
        coefs = dict(x[1])
        for k, v in y[1].items():
            coefs[k] = coefs.get(k, 0.0) + sy * v
        return (c, {k: v for k, v in coefs.items() if v})

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [rd(v) for v in eqn.invars]
        out = None
        if prim in ("add", "add_any") and len(ins) == 2:
            out = comb(ins[0], ins[1], 1.0)
        elif prim == "sub" and len(ins) == 2:
            out = comb(ins[0], ins[1], -1.0)
        elif prim == "mul" and len(ins) == 2:
            for a, b in ((ins[0], ins[1]), (ins[1], ins[0])):
                if a is not None and b is not None and not b[1]:
                    out = (a[0] * b[0], {k: v * b[0]
                                         for k, v in a[1].items() if v})
                    break
        elif prim == "neg" and ins:
            out = comb((0.0, {}), ins[0], -1.0)
        elif prim in ("convert_element_type", "copy", "squeeze", "reshape",
                      "broadcast_in_dim", "stop_gradient") and ins:
            out = ins[0]
        for ov in eqn.outvars:
            aff[id(ov)] = out
    return [rd(v) for v in jaxpr.outvars]


# -- the checks ------------------------------------------------------------

def check_pallas_call(walker, eqn, ins, env):
    """BoundsWalker transfer function for ``pallas_call``: run the four
    kernel checks, emitting through the walker, and return TOP for the
    kernel's outputs (attention math itself is not interval-tracked)."""
    from simple_distributed_machine_learning_tpu.analysis.bounds import (
        TOP,
        _index_verdict,
    )
    n = len(eqn.outvars)
    gm = _grid_mapping(eqn)
    if gm is None:
        return [TOP] * n
    grid = _grid(gm)
    sem = _dimension_semantics(eqn, len(grid))
    n_sp, n_in, n_out, n_scr = _counts(eqn, gm)
    bms = list(getattr(gm, "block_mappings", ()) or ())
    sp_ivs = list(ins[:n_sp])
    src = source_line(eqn)
    mute = walker._mute > 0

    def emit(f):
        if not mute:
            walker._emit(f)

    for i, bm in enumerate(bms):
        parts = _bm_parts(bm)
        closed = _index_map_jaxpr(bm)
        if parts is None or closed is None:
            continue
        block, shape, dtype = parts
        is_out = i >= n_in
        what = (f"output {i - n_in}" if is_out else f"input {i}")
        if _kernel_copies(bm):
            # no block and no index map (the body walk proves the copies);
            # what the kernel moves at a time is one row of the leading
            # axis, and the tiling lint judges it as the caller holds it
            held = (shape if is_out
                    else _held_shape(eqn.invars[n_sp + i], env))
            _tile_lint((1,) + held[1:], dtype, what, src, emit)
            continue

        # (1) index-map bounds proof
        comps = _eval_index_map(walker, closed, grid, sp_ivs)
        for k, iv in enumerate(comps):
            if k >= len(block) or k >= len(shape):
                continue
            n_blocks_k = max(1, -(-shape[k] // max(1, block[k])))
            allowed_hi = n_blocks_k - 1
            verdict = _index_verdict(iv, allowed_hi)
            if verdict == "ok":
                continue
            lo = "-inf" if iv.lo == -_INF else int(iv.lo)
            hi = "inf" if iv.hi == _INF else int(iv.hi)
            if verdict == "oob":
                emit(Finding(
                    rule="kernel-oob.index-map", severity=Severity.ERROR,
                    message=(f"pallas_call {what} index map component {k} "
                             f"has range [{lo}, {hi}] but the backing "
                             f"buffer (shape {shape}, block {block}) only "
                             f"addresses block indices [0, {allowed_hi}] "
                             f"— the kernel would stream a window outside "
                             f"the buffer"),
                    where=src,
                    hint="clamp the index map (the fetch-elision "
                         "jnp.minimum discipline) or tighten the declared "
                         "spec(...) contract on the scalar-prefetch "
                         "operand feeding it"))
            else:
                emit(Finding(
                    rule="kernel-unproven.index-map",
                    severity=Severity.WARNING,
                    message=(f"pallas_call {what} index map component {k} "
                             f"could not be bounded (range [{lo}, {hi}] vs "
                             f"addressable [0, {allowed_hi}]) — the block "
                             f"stream is only as safe as the undeclared "
                             f"operand feeding it"),
                    where=src,
                    hint="declare the scalar-prefetch operand's range via "
                         "analysis.bounds.spec (block tables <= n_blocks, "
                         "positions < max_len) so the proof closes"))

        # (2) grid write-race detection (outputs only)
        if is_out:
            aff = _affine_components(closed, len(grid))
            deps = _dep_axes(closed, len(grid))
            for g, gsize in enumerate(grid):
                if gsize <= 1 or sem[g] != "parallel":
                    continue    # arbitrary axes are sequential: revisiting
                    # a window is the accumulate discipline, not a race
                covered = any(a is not None and a[1].get(g)
                              for a in aff)
                reaches = any(g in d for d in deps)
                if covered:
                    continue
                if reaches:
                    emit(Finding(
                        rule="kernel-race.unproven-map",
                        severity=Severity.WARNING,
                        message=(f"pallas_call {what} index map depends on "
                                 f"parallel grid axis {g} non-affinely — "
                                 f"injectivity (each output window written "
                                 f"by one cell) could not be proven"),
                        where=src,
                        hint="make the output map affine in the parallel "
                             "axis, or mark the axis 'arbitrary' if it "
                             "deliberately accumulates"))
                else:
                    emit(Finding(
                        rule="kernel-race.parallel-overwrite",
                        severity=Severity.ERROR,
                        message=(f"pallas_call {what} index map ignores "
                                 f"parallel grid axis {g} (size {gsize}): "
                                 f"every cell of that axis writes the SAME "
                                 f"output window concurrently — last "
                                 f"writer wins, nondeterministically"),
                        where=src,
                        hint="index the output block by the parallel axis, "
                             "or declare the axis 'arbitrary' in "
                             "dimension_semantics so Mosaic serializes it "
                             "for an accumulate discipline"))

        _tile_lint(block, dtype, what, src, emit)

    # (4) dtype lint: sub-f32 floating scratch accumulators
    body = eqn.params.get("jaxpr")
    body_jaxpr = getattr(body, "jaxpr", body)
    if body_jaxpr is not None and n_scr:
        for v in list(body_jaxpr.invars)[-n_scr:]:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            try:
                dt = np.dtype(dt)
            except TypeError:
                continue                # a semaphore
            if dt.kind == "f" and is_low_precision(dt):
                emit(Finding(
                    rule="kernel-dtype-drift.low-precision-scratch",
                    severity=Severity.WARNING,
                    message=(f"pallas_call carries "
                             f"{np.dtype(dt).name} scratch "
                             f"{tuple(getattr(aval, 'shape', ()))} across "
                             f"grid iterations — online-softmax state "
                             f"accumulated below f32 drifts from the "
                             f"dense path's einsum promotion (the "
                             f"bit-exactness contract)"),
                    where=src,
                    hint="allocate the accumulator/l/m scratch as "
                         "pltpu.VMEM(..., jnp.float32) and cast only on "
                         "the final store"))

    # (5) the kernel's own copies: walk the body, scalar-prefetch refs
    # seeded from the caller's contracts; ``check_dma_start`` does the rest
    if body_jaxpr is not None and any(_kernel_copies(bm) for bm in bms):
        ivs = sp_ivs + [TOP] * (len(body_jaxpr.invars) - len(sp_ivs))
        walker._kernels.append(_kernel_name(eqn))
        try:
            walker._walk(body_jaxpr, walker._sub_env(body, ivs))
        finally:
            walker._kernels.pop()
    return [TOP] * n


def _tile_lint(block, dtype, what, src, emit) -> None:
    """(3) tiling lint: Mosaic pads the trailing two dims to the dtype's
    minimum tile; compare against the transposed layout."""
    if len(block) < 2:
        return
    sub, lane = block[-2], block[-1]
    if sub <= 0 or lane <= 0:
        return
    st, lt = _min_tile(dtype)
    waste = (_roundup(sub, st) * _roundup(lane, lt)) / (sub * lane)
    waste_t = (_roundup(lane, st) * _roundup(sub, lt)) / (sub * lane)
    if waste >= _WASTE_FLAG and waste >= _WASTE_RATIO * waste_t:
        emit(Finding(
            rule="kernel-tile.pad-waste",
            severity=Severity.WARNING,
            message=(f"pallas_call {what} block {block} "
                     f"({dtype.name}) pads to the "
                     f"({st},{lt}) minimum tile at {waste:.0f}x "
                     f"its size — transposing the trailing "
                     f"dims would pad only {waste_t:.0f}x (the "
                     f"small-head-dim-in-the-lane-slot "
                     f"hazard)"),
            where=src,
            hint="swap the trailing block dims (pack the "
                 "small dim into sublanes, the long one into "
                 "lanes), or widen the row: the paged pool "
                 "keeps all heads of a position in one row "
                 "(serve/slots.py::PagedKVPool)"))


def _held_shape(var, env) -> tuple[int, ...]:
    """A kernel input's shape as the caller holds it: before the rows were
    padded to whole lane tiles on the way in, where they were."""
    made = env.producer.get(id(var))
    if made is not None and made.primitive.name == "pad":
        var = made.invars[0]
    return tuple(int(d) for d in var.aval.shape)


def check_dma_start(walker, eqn, env) -> None:
    """BoundsWalker transfer function for a kernel's ``dma_start``: where
    the source is an unblocked operand, every indexed dimension's interval
    must lie inside the operand (the block-table contract reaches the copy
    through the ``get`` on the scalar-prefetch ref)."""
    import jax

    from simple_distributed_machine_learning_tpu.analysis.bounds import (
        Interval,
        _index_verdict,
    )
    if walker._mute > 0:
        return
    src_ref, src_tf = jax.tree_util.tree_unflatten(
        eqn.params["tree"], eqn.invars)[:2]
    aval = src_ref.aval
    if not _unblocked(aval) or not src_tf:
        return                          # not the pool, or all of it
    name = walker._kernels[-1] if walker._kernels else "pallas_call"
    where = source_line(eqn)
    shape = tuple(int(d) for d in aval.shape)
    idx = getattr(src_tf[0], "indices", None) if len(src_tf) == 1 else None
    if idx is None or tuple(src_tf[0].shape) != shape:
        walker._emit(Finding(
            rule="kernel-unproven.dma-source", severity=Severity.WARNING,
            message=(f"{name}: a copy reads its unblocked operand {shape} "
                     f"through a view the rule does not follow — nothing "
                     f"proven"),
            where=where,
            hint="index the operand with one .at[...] of scalars and "
                 "pl.ds slices"))
        return
    for d, (ix, dim) in enumerate(zip(idx, shape)):
        size = int(getattr(ix, "size", 1))
        start = getattr(ix, "start", ix)
        iv = (Interval(start, start) if isinstance(start, int)
              else env.read(start))
        verdict = _index_verdict(iv, dim - size)
        if verdict == "ok":
            continue
        oob = verdict == "oob"
        lo = "-inf" if iv.lo == -_INF else int(iv.lo)
        hi = "inf" if iv.hi == _INF else int(iv.hi)
        walker._emit(Finding(
            rule=("kernel-oob.dma-source" if oob
                  else "kernel-unproven.dma-source"),
            severity=Severity.ERROR if oob else Severity.WARNING,
            message=(f"{name}: a copy of {size} along dimension {d} of "
                     f"its unblocked operand {shape} starts in [{lo}, "
                     f"{hi}], only [0, {dim - size}] is addressable — "
                     + ("the kernel would copy a window outside the "
                        "buffer" if oob else
                        "the copy is only as safe as the undeclared "
                        "operand feeding it")),
            where=where,
            hint="clamp the index in the kernel, or tighten the declared "
                 "spec(...) contract on the scalar-prefetch operand "
                 "feeding it (block tables <= n_blocks)"))


def _roundup(x: int, q: int) -> int:
    return -(-x // q) * q


def _min_tile(dtype: np.dtype) -> tuple[int, int]:
    """Mosaic minimum (sublane, lane) tile for a dtype (pallas guide:
    f32 (8,128), bf16/f16 (16,128), int8/fp8 (32,128))."""
    size = np.dtype(dtype).itemsize
    if size >= 4:
        return 8, _LANE
    if size == 2:
        return 16, _LANE
    return 32, _LANE


# -- kernel-derived HBM cost rows -----------------------------------------

def _uses_scalar_prefetch(closed, n_grid: int) -> bool:
    """True when the index map dereferences a scalar-prefetch ref (a
    ``get`` on an invar past the grid axes) — the table-indexed K/V
    stream signature."""
    jaxpr = getattr(closed, "jaxpr", closed)
    refs = {id(v) for v in list(jaxpr.invars)[n_grid:]}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "get" and eqn.invars \
                and id(eqn.invars[0]) in refs:
            return True
    return False


def kernel_hbm_costs(closed_jaxpr, program: str = "") -> list[HBMCost]:
    """Derive HBM bytes-per-run rows from every ``pallas_call`` in a traced
    program: each BlockMapping moves ``prod(block) * itemsize`` bytes once
    per distinct index-map value, i.e. per cell of the grid axes the map
    actually depends on (axes it ignores revisit the same window — Mosaic
    elides the copy, and so does this model). Streams whose index map
    dereferences a scalar-prefetch operand (the block-table signature) are
    tagged ``kernel.kv_stream``; everything else ``kernel.io``. Enclosing
    ``scan`` trip counts multiply through."""
    kv = 0
    io = 0
    calls = 0

    def walk(jaxpr, trips):
        nonlocal kv, io, calls
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = _grid_mapping(eqn)
                if gm is None:
                    continue
                calls += 1
                grid = _grid(gm)
                n_sp, n_in, n_out, _ = _counts(eqn, gm)
                seen = 0
                copies = False
                for i, bm in enumerate(getattr(gm, "block_mappings", ())
                                       or ()):
                    parts = _bm_parts(bm)
                    closed = _index_map_jaxpr(bm)
                    if parts is None or closed is None:
                        continue
                    if _kernel_copies(bm):
                        copies = True
                        continue
                    block, _shape, dtype = parts
                    deps = frozenset().union(
                        *_dep_axes(closed, len(grid))) \
                        if grid else frozenset()
                    nbytes = int(np.prod(block)) * dtype.itemsize
                    for g in deps:
                        if g < len(grid):
                            nbytes *= grid[g]
                    seen += nbytes
                    if i < n_in and _uses_scalar_prefetch(closed,
                                                          len(grid)):
                        kv += nbytes * trips
                    else:
                        io += nbytes * trips
                # what the kernel copies itself no BlockSpec shows: the
                # call declares all it moves, the blocks seen are the rest
                cost = eqn.params.get("cost_estimate")
                if copies and cost is not None:
                    kv += max(0, int(cost.bytes_accessed) - seen) * trips
                continue
            mult = 1
            if eqn.primitive.name == "scan":
                mult = int(eqn.params.get("length", 1) or 1)
            for _key, _i, sub in subjaxprs(eqn):
                walk(getattr(sub, "jaxpr", sub), trips * mult)

    walk(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), 1)
    if not calls:
        return []
    rows = [HBMCost(
        op="kernel.kv_stream", program=program, bytes_per_tick=kv,
        note=f"{calls} pallas_call(s): table-indexed K/V blocks x the "
             f"grid trips their index maps depend on, from the kernels' "
             f"own BlockSpecs; for operands a kernel copies itself, its "
             f"declared bytes less the blocks seen")]
    if io:
        rows.append(HBMCost(
            op="kernel.io", program=program, bytes_per_tick=io,
            note="non-table kernel operand/output blocks x grid trips"))
    return rows
