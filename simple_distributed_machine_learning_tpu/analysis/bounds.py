"""Dataflow interval analysis over jaxprs — the ``scatter-bounds`` rule.

The serving programs' one irreducible hazard is an index: a block-table
entry feeding a K/V scatter, a position counter feeding a
``dynamic_update_slice``, a sampled token feeding an embedding gather. XLA
never raises on an out-of-range index — depending on the op's mode it
CLAMPS (the write silently lands on the last row: another request's K/V),
DROPS (the write vanishes: attention reads stale garbage), or is outright
undefined (``PROMISE_IN_BOUNDS``, which the paged block gathers use). The
pool's Python guards (``serve/slots.py``) keep the HOST-side tables inside
the contract; this pass machine-checks that the COMPILED programs respect
it: given declared value ranges for the index-bearing inputs (``spec``),
interval arithmetic is propagated through every equation and every
gather/scatter/dynamic-slice start index is proven inside its operand's
bounds.

Contract declaration — wrap any abstract arg the caller can bound::

    from simple_distributed_machine_learning_tpu.analysis import bounds
    tables = bounds.spec((S, NB), np.int32, 0, n_blocks)   # table entries
    pos    = bounds.spec((S,),    np.int32, 0, max_len - 1)
    report = analysis.analyze(step_fn, params_sds, kc, vc, toks, pos,
                              tables, ...)

Findings:

- ``scatter-bounds.out-of-range`` (ERROR) — an index interval provably
  reaches outside ``[0, dim - window]``: the write/read lands in (or
  silently clamps onto) memory belonging to someone else;
- ``scatter-bounds.unproven-promise`` (WARNING) — a ``PROMISE_IN_BOUNDS``
  gather/scatter whose index interval the analysis cannot bound: the
  program promises XLA something nobody proved.

The propagation is deliberately conservative: unknown values are
``[-inf, inf]``, unhandled primitives produce unknowns, scan/while carries
run a widening fixpoint — the pass can miss a proof (a WARNING at worst)
but never claims safety it did not derive.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import numpy as np

from simple_distributed_machine_learning_tpu.analysis.report import (
    Finding,
    Severity,
)
from simple_distributed_machine_learning_tpu.analysis.trace import (
    source_line,
    subjaxprs,
)

_INF = math.inf


@dataclasses.dataclass(frozen=True)
class Interval:
    """Inclusive value bounds; ``[-inf, inf]`` is the unknown (TOP)."""
    lo: float
    hi: float

    @property
    def known(self) -> bool:
        return self.lo > -_INF or self.hi < _INF

    def __or__(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))


TOP = Interval(-_INF, _INF)
BOOL = Interval(0, 1)


@dataclasses.dataclass(frozen=True)
class ArgSpec:
    """An abstract argument plus its declared value contract.

    ``lo``/``hi`` are the inclusive bounds the CALLER guarantees for every
    element (the host-side discipline being machine-checked); ``vary`` are
    mesh axes the buffer's CONTENT differs over even though its shape is
    replicated (the sharded-state rule's seed — a ZeRO shard passed as a
    full-shape buffer)."""
    sds: Any
    lo: float | None = None
    hi: float | None = None
    vary: tuple = ()

    @property
    def interval(self) -> Interval | None:
        if self.lo is None and self.hi is None:
            return None
        return Interval(-_INF if self.lo is None else self.lo,
                        _INF if self.hi is None else self.hi)


def spec(shape, dtype, lo=None, hi=None, vary=()) -> ArgSpec:
    """A ``ShapeDtypeStruct`` carrying a value contract (see ArgSpec)."""
    import jax
    return ArgSpec(jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype)),
                   lo=lo, hi=hi, vary=tuple(vary))


def _const_interval(val) -> Interval:
    try:
        a = np.asarray(val)
        if a.size == 0 or a.dtype.kind not in "iub":
            return TOP
        return Interval(float(a.min()), float(a.max()))
    except Exception:
        return TOP


class _Env:
    """Interval state for one jaxpr body: per-var intervals, concrete
    values for small integer constants (per-component index recovery), and
    the concatenate decomposition of index vectors."""

    def __init__(self):
        self.iv: dict[int, Interval] = {}
        self.concrete: dict[int, np.ndarray] = {}
        self.parts: dict[int, list[tuple[int, Interval]]] = {}
        self.producer: dict[int, Any] = {}      # var -> the eqn that made it

    def read(self, atom) -> Interval:
        if hasattr(atom, "val"):            # Literal (has .aval too)
            return _const_interval(atom.val)
        return self.iv.get(id(atom), TOP)

    def read_concrete(self, atom) -> np.ndarray | None:
        if hasattr(atom, "val"):
            v = np.asarray(atom.val)
            return v if v.dtype.kind in "iub" else None
        return self.concrete.get(id(atom))

    def seed_consts(self, jaxpr, consts) -> None:
        """Constvars get their actual values: intervals always, the whole
        array when it is a small integer one (per-component index-vector
        recovery, e.g. a literal ``[layer, 0]`` scatter index)."""
        for var, val in zip(jaxpr.constvars, consts):
            self.iv[id(var)] = _const_interval(val)
            try:
                arr = np.asarray(val)
            except Exception:
                continue
            if arr.ndim <= 2 and arr.size <= 4096 and arr.dtype.kind in "iub":
                self.concrete[id(var)] = arr


def _mul_iv(a: Interval, b: Interval) -> Interval:
    prods = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            if (x in (0, -0.0) or y in (0, -0.0)):
                prods.append(0.0)
            elif abs(x) == _INF or abs(y) == _INF:
                prods.append(_INF if (x > 0) == (y > 0) else -_INF)
            else:
                prods.append(x * y)
    return Interval(min(prods), max(prods))


def _div_iv(a: Interval, b: Interval) -> Interval:
    # only the shape the index programs use: a known nonneg dividend over a
    # positive constant divisor (pos // block_size)
    if b.lo == b.hi and b.lo > 0 and a.lo >= 0 and a.known:
        c = b.lo
        hi = a.hi if a.hi == _INF else float(int(a.hi // c))
        return Interval(float(int(a.lo // c)), hi)
    return TOP


def _floordiv_iv(a: Interval, b: Interval) -> Interval:
    # any-sign dividend over a positive constant divisor
    if b.lo == b.hi and b.lo > 0 and a.known:
        c = b.lo
        lo = a.lo if a.lo == -_INF else float(math.floor(a.lo / c))
        hi = a.hi if a.hi == _INF else float(math.floor(a.hi / c))
        return Interval(lo, hi)
    return TOP


def _mod_iv(a: Interval, b: Interval) -> Interval:
    # Python-semantics mod (sign follows the divisor)
    if b.lo == b.hi and b.lo > 0:
        return Interval(0, b.lo - 1)
    return TOP


def _cmp_iv(prim: str, a: Interval, b: Interval) -> Interval:
    """Comparison result interval: [0,0]/[1,1] when the operand intervals
    decide it, else the unknown bool [0,1]."""
    if prim == "lt":
        if a.hi < b.lo:
            return Interval(1, 1)
        if a.lo >= b.hi:
            return Interval(0, 0)
    elif prim == "le":
        if a.hi <= b.lo:
            return Interval(1, 1)
        if a.lo > b.hi:
            return Interval(0, 0)
    elif prim == "gt":
        if a.lo > b.hi:
            return Interval(1, 1)
        if a.hi <= b.lo:
            return Interval(0, 0)
    elif prim == "ge":
        if a.lo >= b.hi:
            return Interval(1, 1)
        if a.hi < b.lo:
            return Interval(0, 0)
    elif prim == "eq":
        if a.lo == a.hi == b.lo == b.hi:
            return Interval(1, 1)
        if a.hi < b.lo or b.hi < a.lo:
            return Interval(0, 0)
    elif prim == "ne":
        if a.lo == a.hi == b.lo == b.hi:
            return Interval(0, 0)
        if a.hi < b.lo or b.hi < a.lo:
            return Interval(1, 1)
    return BOOL


def _rem_iv(a: Interval, b: Interval) -> Interval:
    # lax.rem's sign follows the dividend
    if b.lo == b.hi and b.lo > 0:
        c = b.lo
        hi = min(a.hi, c - 1) if a.hi < _INF else c - 1
        if a.lo >= 0:
            return Interval(0.0, max(0.0, hi))
        return Interval(-(c - 1), c - 1)
    return TOP


def _index_verdict(iv: Interval, allowed_hi: int) -> str:
    """Classify an index interval against ``[0, allowed_hi]``.

    ``"ok"`` — provably in bounds. ``"oob"`` — the violation is carried by
    a FINITE bound (a declared/derived range that genuinely reaches outside
    the operand). ``"unproven"`` — the only violating side is infinite:
    nothing was proven either way, so a half-declared contract (only ``lo``
    or only ``hi``) degrades to the same not-proven treatment as no
    contract at all instead of escalating to a gating ERROR."""
    if iv.lo >= 0 and iv.hi <= allowed_hi:
        return "ok"
    if iv.lo > allowed_hi or iv.hi < 0:
        return "oob"                    # EVERY possible value is outside
    if (iv.lo < 0 and iv.lo > -_INF) or (allowed_hi < iv.hi < _INF):
        return "oob"                    # a finite declared bound reaches out
    return "unproven"


_MODE_EFFECT = {
    "GatherScatterMode.CLIP": "the index CLAMPS to the edge — the access "
                              "silently lands on the last row in bounds",
    "GatherScatterMode.FILL_OR_DROP": "the write is silently DROPPED (or "
                                      "the read filled) — downstream math "
                                      "consumes stale garbage",
    "GatherScatterMode.PROMISE_IN_BOUNDS": "the program PROMISED XLA the "
                                           "index is in bounds — out of "
                                           "range is undefined behavior",
}


class BoundsWalker:
    """One interval-propagation pass; findings accumulate on ``emit``."""

    def __init__(self, emit: Callable[..., None]):
        self._emit = emit
        self._mute = 0
        self._kernels: list[str] = []   # the pallas_calls being walked

    # -- body walk --------------------------------------------------------

    def run(self, closed_jaxpr, in_ranges: list[Interval | None]):
        jaxpr = closed_jaxpr.jaxpr
        env = _Env()
        env.seed_consts(jaxpr, closed_jaxpr.consts)
        ivs = list(in_ranges) + [None] * (len(jaxpr.invars) - len(in_ranges))
        for var, iv in zip(jaxpr.invars, ivs):
            env.iv[id(var)] = iv if iv is not None else TOP
        outs = self._walk(jaxpr, env)
        return outs

    def _walk(self, jaxpr, env: _Env) -> list[Interval]:
        for eqn in jaxpr.eqns:
            outs = self._eqn(eqn, env)
            for var, iv in zip(eqn.outvars, outs):
                env.iv[id(var)] = iv
                env.producer[id(var)] = eqn
        return [env.read(v) for v in jaxpr.outvars]

    def _sub_env(self, sub_closed_or_open, in_ivs: list[Interval]) -> _Env:
        env = _Env()
        jaxpr = getattr(sub_closed_or_open, "jaxpr", sub_closed_or_open)
        env.seed_consts(jaxpr, getattr(sub_closed_or_open, "consts", ()))
        for var, iv in zip(jaxpr.invars, in_ivs):
            env.iv[id(var)] = iv
        return env

    def _call_sub(self, sub, in_ivs) -> list[Interval]:
        jaxpr = getattr(sub, "jaxpr", sub)
        env = self._sub_env(sub, in_ivs)
        self._walk(jaxpr, env)
        return [env.read(v) for v in jaxpr.outvars]

    # -- per-equation transfer function -----------------------------------

    def _eqn(self, eqn, env: _Env) -> list[Interval]:
        prim = eqn.primitive.name
        ins = [env.read(v) for v in eqn.invars]
        union = Interval(min((i.lo for i in ins), default=-_INF),
                         max((i.hi for i in ins), default=_INF)) \
            if ins else TOP
        n = len(eqn.outvars)
        a = ins[0] if ins else TOP

        if prim in ("add", "add_any"):
            return [Interval(ins[0].lo + ins[1].lo, ins[0].hi + ins[1].hi)] * n
        if prim == "sub":
            return [Interval(ins[0].lo - ins[1].hi, ins[0].hi - ins[1].lo)] * n
        if prim == "mul":
            return [_mul_iv(ins[0], ins[1])] * n
        if prim == "div":
            return [_div_iv(ins[0], ins[1])] * n
        if prim == "rem":
            return [_rem_iv(ins[0], ins[1])] * n
        if prim == "neg":
            return [Interval(-a.hi, -a.lo)] * n
        if prim == "sign":
            lo = -1 if a.lo < 0 else (0 if a.lo == 0 else 1)
            hi = 1 if a.hi > 0 else (0 if a.hi == 0 else -1)
            return [Interval(lo, hi)] * n
        if prim == "max":
            return [Interval(max(ins[0].lo, ins[1].lo),
                             max(ins[0].hi, ins[1].hi))] * n
        if prim == "min":
            return [Interval(min(ins[0].lo, ins[1].lo),
                             min(ins[0].hi, ins[1].hi))] * n
        if prim == "clamp":
            lo_b, x, hi_b = ins
            m = Interval(max(x.lo, lo_b.lo), max(x.hi, lo_b.hi))
            return [Interval(min(m.lo, hi_b.lo), min(m.hi, hi_b.hi))] * n
        if prim in ("eq", "ne", "lt", "le", "gt", "ge"):
            # decidable comparisons matter: jnp's negative-index
            # normalization is `where(idx < 0, idx + N, idx)`, and proving
            # the predicate constant-false is what keeps a declared
            # in-bounds index from widening to [lo, hi + N]
            return [_cmp_iv(prim, ins[0], ins[1])] * n
        if prim in ("is_finite", "not", "reduce_and", "reduce_or"):
            return [BOOL] * n
        if prim in ("and", "or", "xor"):
            aval = getattr(eqn.outvars[0], "aval", None)
            if aval is not None and np.dtype(aval.dtype).kind == "b":
                if prim == "and":
                    return [Interval(min(ins[0].lo, ins[1].lo),
                                     min(ins[0].hi, ins[1].hi))] * n
                if prim == "or":
                    return [Interval(max(ins[0].lo, ins[1].lo),
                                     max(ins[0].hi, ins[1].hi))] * n
                return [BOOL] * n
            return [TOP] * n
        if prim == "select_n":
            pred, cases = ins[0], ins[1:]
            if pred.lo == pred.hi and 0 <= pred.lo < len(cases):
                return [cases[int(pred.lo)]] * n    # decided predicate
            out = cases[0]
            for c in cases[1:]:
                out = out | c
            return [out] * n
        if prim in ("broadcast_in_dim", "reshape", "transpose", "squeeze",
                    "rev", "slice", "copy", "stop_gradient",
                    "reduce_max", "reduce_min", "sort", "expand_dims",
                    "reduce_precision", "real", "optimization_barrier",
                    "pvary"):       # the vma varying cast: value-identity
            if prim == "sort":
                return [env.read(v) for v in eqn.invars][:n] or [a] * n
            return [a] * n
        if prim == "convert_element_type":
            src = getattr(eqn.invars[0], "aval", None)
            dst = getattr(eqn.outvars[0], "aval", None)
            if (src is not None and dst is not None
                    and np.dtype(src.dtype).kind in "iub"):
                dk = np.dtype(dst.dtype)
                if dk.kind == "b":
                    return [BOOL] * n
                if dk.kind in "iu":
                    # a narrowing cast WRAPS at runtime: the interval
                    # survives only when provably representable in the
                    # destination dtype, else nothing is known
                    info = np.iinfo(dk)
                    if a.lo >= info.min and a.hi <= info.max:
                        return [a] * n
                    return [TOP] * n
                return [a] * n
            return [TOP] * n
        if prim == "iota":
            dim = eqn.params.get("dimension", 0)
            shape = eqn.params.get("shape") or eqn.outvars[0].aval.shape
            size = shape[dim] if shape else 1
            return [Interval(0, max(0, size - 1))] * n
        if prim in ("argmax", "argmin"):
            axes = eqn.params.get("axes", (0,))
            size = eqn.invars[0].aval.shape[int(axes[0])]
            return [Interval(0, max(0, size - 1))] * n
        if prim == "top_k":
            # (values, indices)
            size = eqn.invars[0].aval.shape[-1]
            out = [a, Interval(0, max(0, size - 1))]
            return out[:n] + [TOP] * (n - len(out))
        if prim == "concatenate":
            dim = eqn.params.get("dimension", 0)
            out_aval = getattr(eqn.outvars[0], "aval", None)
            if out_aval is not None and dim == len(out_aval.shape) - 1:
                env.parts[id(eqn.outvars[0])] = [
                    (int(v.aval.shape[-1]), env.read(v))
                    for v in eqn.invars]
            return [union] * n
        if prim == "pad":
            return [ins[0] | ins[1]] * n
        if prim == "gather":
            self._check_gather(eqn, env)
            return [a] * n
        if prim == "scatter":
            self._check_scatter(eqn, env)
            return [ins[0] | ins[2]] * n
        if prim in ("scatter-add", "scatter_add", "scatter-mul",
                    "scatter_mul", "scatter-min", "scatter_min",
                    "scatter-max", "scatter_max"):
            self._check_scatter(eqn, env)
            return [TOP] * n
        if prim == "dynamic_slice":
            self._check_dynamic(eqn, env, has_update=False)
            return [a] * n
        if prim == "dynamic_update_slice":
            self._check_dynamic(eqn, env, has_update=True)
            return [ins[0] | ins[1]] * n
        if prim == "scan":
            return self._scan(eqn, env)
        if prim == "while":
            return self._while(eqn, env)
        if prim == "cond":
            return self._cond(eqn, env)
        if prim == "get":
            # Pallas ref read (SMEM scalar-prefetch deref in index maps):
            # values drawn from the ref carry the ref's content interval
            return [a] * n
        if prim == "dma_start":
            # a kernel's own copy (operands in pl.ANY): its source window
            # is proven like a BlockSpec index map (analysis/kernels.py)
            from simple_distributed_machine_learning_tpu.analysis import (
                kernels,
            )
            kernels.check_dma_start(self, eqn, env)
            return []
        if prim == "pallas_call":
            # open the kernel box: index-map bounds proofs, write-race
            # detection, tiling/dtype lint (analysis/kernels.py)
            from simple_distributed_machine_learning_tpu.analysis import (
                kernels,
            )
            return kernels.check_pallas_call(self, eqn, ins, env)

        if prim == "jit" and len(ins) == 2:
            # jnp's floor_divide/remainder lower to div/rem plus a
            # sign-correction select whose predicate is only RELATIONALLY
            # decidable (sign(d) != sign(c) AND rem != 0 share d) — plain
            # interval propagation widens it; compute the closed form
            name = eqn.params.get("name")
            if name == "floor_divide":
                return [_floordiv_iv(ins[0], ins[1])] * n
            if name == "remainder":
                return [_mod_iv(ins[0], ins[1])] * n

        # generic call-like primitives: recurse when the arity matches
        for _key, _i, sub in subjaxprs(eqn):
            closed = eqn.params.get(_key)
            closed = (closed if not isinstance(closed, (tuple, list))
                      else closed[_i])
            target = getattr(closed, "jaxpr", closed)
            if len(target.invars) == len(eqn.invars):
                outs = self._call_sub(closed, ins)
                if len(outs) >= n:
                    return outs[:n]
        return [TOP] * n

    # -- control flow -----------------------------------------------------

    def _scan(self, eqn, env: _Env) -> list[Interval]:
        p = eqn.params
        body = p["jaxpr"]
        nc, ncar = p.get("num_consts", 0), p.get("num_carry", 0)
        ins = [env.read(v) for v in eqn.invars]
        consts, carry, xs = ins[:nc], list(ins[nc:nc + ncar]), ins[nc + ncar:]
        # an xs row's values are bounded by the whole stacked array's
        outs = None
        self._mute += 1
        try:
            for it in range(8):
                outs = self._call_sub(body, consts + carry + xs)
                new_carry = [c | o for c, o in zip(carry, outs[:ncar])]
                if new_carry == carry:
                    break
                carry = new_carry
            else:
                carry = [TOP] * ncar          # widen: no fixpoint reached
        finally:
            self._mute -= 1
        outs = self._call_sub(body, consts + carry + xs)
        return carry + outs[ncar:]

    def _while(self, eqn, env: _Env) -> list[Interval]:
        p = eqn.params
        cnc, bnc = p.get("cond_nconsts", 0), p.get("body_nconsts", 0)
        ins = [env.read(v) for v in eqn.invars]
        body_consts = ins[cnc:cnc + bnc]
        carry = list(ins[cnc + bnc:])
        self._mute += 1
        try:
            for it in range(8):
                outs = self._call_sub(p["body_jaxpr"], body_consts + carry)
                new_carry = [c | o for c, o in zip(carry, outs)]
                if new_carry == carry:
                    break
                carry = new_carry
            else:
                carry = [TOP] * len(carry)
        finally:
            self._mute -= 1
        # findings passes over the post-fixpoint carry — the cond jaxpr is
        # a program too (an index-bearing read in the loop predicate must
        # not analyze vacuously clean)
        self._call_sub(p["body_jaxpr"], body_consts + carry)
        self._call_sub(p["cond_jaxpr"], ins[:cnc] + carry)
        return carry

    def _cond(self, eqn, env: _Env) -> list[Interval]:
        ins = [env.read(v) for v in eqn.invars]
        op_ivs = ins[1:]
        outs = None
        for branch in eqn.params.get("branches") or ():
            b_outs = self._call_sub(branch, op_ivs)
            outs = (b_outs if outs is None
                    else [x | y for x, y in zip(outs, b_outs)])
        return outs if outs is not None else [TOP] * len(eqn.outvars)

    # -- the index checks -------------------------------------------------

    def _components(self, eqn, idx_atom, n_comp, env: _Env
                    ) -> list[Interval] | None:
        """Per-component intervals of an index vector: exact for concrete
        constants, whole-array for single components, recovered from the
        ``concatenate`` that built the vector otherwise."""
        conc = env.read_concrete(idx_atom)
        if conc is not None and conc.shape and conc.shape[-1] == n_comp:
            return [Interval(float(conc[..., k].min()),
                             float(conc[..., k].max()))
                    for k in range(n_comp)]
        if conc is not None and conc.ndim == 1 and conc.shape[0] == n_comp:
            return [Interval(float(v), float(v)) for v in conc]
        if n_comp == 1:
            return [env.read(idx_atom)]
        parts = env.parts.get(id(idx_atom))
        if parts is not None and sum(w for w, _ in parts) == n_comp:
            out = []
            for width, iv in parts:
                out.extend([iv] * width)
            return out
        return None

    def _flag(self, eqn, what: str, comp: int, dim: int, iv: Interval,
              allowed_hi: int, mode) -> None:
        if self._mute:
            return
        op_shape = eqn.invars[0].aval.shape
        effect = _MODE_EFFECT.get(str(mode), "out-of-bounds behavior is "
                                             "backend-defined")
        src = source_line(eqn)
        lo = "-inf" if iv.lo == -_INF else int(iv.lo)
        hi = "inf" if iv.hi == _INF else int(iv.hi)
        self._emit(Finding(
            rule="scatter-bounds.out-of-range", severity=Severity.ERROR,
            message=(f"{what} index component {comp} into operand dim {dim} "
                     f"(shape {tuple(op_shape)}) has range [{lo}, {hi}] but "
                     f"only [0, {allowed_hi}] is addressable — {effect}"),
            where=src,
            hint="tighten the producing arithmetic or the declared input "
                 "contract (analysis.bounds.spec) so the index interval "
                 "fits; for K/V writes this is the slots.py block/position "
                 "discipline the compiled program must not outrun"))

    def _flag_unproven(self, eqn, what: str) -> None:
        if self._mute:
            return
        self._emit(Finding(
            rule="scatter-bounds.unproven-promise", severity=Severity.WARNING,
            message=(f"{what} runs in PROMISE_IN_BOUNDS mode but the index "
                     f"interval could not be bounded — an out-of-range "
                     f"index here is undefined behavior"),
            where=source_line(eqn),
            hint="declare the index-bearing input's range via "
                 "analysis.bounds.spec (or clamp in-program) so the "
                 "promise is provable"))

    def _check_gather(self, eqn, env: _Env) -> None:
        dn = eqn.params.get("dimension_numbers")
        slice_sizes = eqn.params.get("slice_sizes") or ()
        mode = eqn.params.get("mode")
        if dn is None:
            return
        start_map = tuple(dn.start_index_map)
        comps = self._components(eqn, eqn.invars[1], len(start_map), env)
        op_shape = eqn.invars[0].aval.shape
        for k, d in enumerate(start_map):
            win = slice_sizes[d] if d < len(slice_sizes) else 1
            allowed_hi = int(op_shape[d]) - int(win)
            iv = comps[k] if comps is not None else TOP
            verdict = _index_verdict(iv, allowed_hi)
            if verdict == "oob":
                self._flag(eqn, "gather", k, d, iv, allowed_hi, mode)
            elif verdict == "unproven" and "PROMISE" in str(mode):
                self._flag_unproven(eqn, "gather")

    def _check_scatter(self, eqn, env: _Env) -> None:
        dn = eqn.params.get("dimension_numbers")
        mode = eqn.params.get("mode")
        if dn is None:
            return
        sdod = tuple(dn.scatter_dims_to_operand_dims)
        inserted = set(dn.inserted_window_dims)
        batching = set(getattr(dn, "operand_batching_dims", ()) or ())
        op_shape = eqn.invars[0].aval.shape
        upd_shape = eqn.invars[2].aval.shape
        uwd = tuple(dn.update_window_dims)
        # map each non-inserted, non-batching operand dim to its window size
        window = {}
        j = 0
        for d in range(len(op_shape)):
            if d in inserted or d in batching:
                window[d] = 1
                continue
            window[d] = upd_shape[uwd[j]] if j < len(uwd) else 1
            j += 1
        comps = self._components(eqn, eqn.invars[1], len(sdod), env)
        for k, d in enumerate(sdod):
            allowed_hi = int(op_shape[d]) - int(window.get(d, 1))
            iv = comps[k] if comps is not None else TOP
            verdict = _index_verdict(iv, allowed_hi)
            if verdict == "oob":
                self._flag(eqn, "scatter", k, d, iv, allowed_hi, mode)
            elif verdict == "unproven" and "PROMISE" in str(mode):
                self._flag_unproven(eqn, "scatter")

    def _check_dynamic(self, eqn, env: _Env, has_update: bool) -> None:
        op = eqn.invars[0].aval.shape
        if has_update:
            windows = eqn.invars[1].aval.shape
            starts = eqn.invars[2:]
        else:
            windows = eqn.params.get("slice_sizes") or ()
            starts = eqn.invars[1:]
        what = "dynamic_update_slice" if has_update else "dynamic_slice"
        for d, start in enumerate(starts):
            win = windows[d] if d < len(windows) else 1
            allowed_hi = int(op[d]) - int(win)
            iv = env.read(start)
            if _index_verdict(iv, allowed_hi) == "oob":
                self._flag(eqn, what, d, d, iv, allowed_hi,
                           "GatherScatterMode.CLIP")
            # unproven: XLA clamps dynamic-slice starts; nothing to promise


def check_bounds(closed_jaxpr, in_ranges: list[Interval | None]
                 ) -> list[Finding]:
    """Run the interval pass over a traced program given declared input
    ranges (aligned with the jaxpr's flat invars; ``None`` = unknown).
    Returns scatter-bounds findings; an empty list is a PROOF relative to
    the declared contract, not an absence of checking."""
    findings: list[Finding] = []
    BoundsWalker(findings.append).run(closed_jaxpr, in_ranges)
    return findings
