"""Jaxpr tracing and traversal helpers for the static analyzer.

The analyzer never runs the step — it traces it to a ``ClosedJaxpr``
(:func:`trace_to_jaxpr`) and walks equations, recursing through every
sub-jaxpr a primitive carries (``scan``/``cond``/``switch`` bodies, ``jit``
and ``custom_vjp`` call jaxprs, ``shard_map`` inner jaxprs, ``remat``
thunks). Param keys are probed, never assumed.
"""

from __future__ import annotations

from typing import Any, Iterator

import jax
from jax.extend import core as jex_core

# collectives the lint passes care about, by primitive name
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmin", "pmax", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
})
# collectives that are a cross-device rendezvous (axis_index is free)
RENDEZVOUS_PRIMS = COLLECTIVE_PRIMS - {"axis_index"}

# under the vma type system the reducing collectives trace under their
# ``*_invariant`` names (same wire traffic, invariant-typed result); the
# rules speak the classic names
_CANONICAL_PRIM = {
    "psum_invariant": "psum",
    "all_gather_invariant": "all_gather",
}


def prim_name(eqn) -> str:
    """The equation's primitive under the name the rules are written in."""
    name = eqn.primitive.name
    return _CANONICAL_PRIM.get(name, name)


def is_jaxpr(x: Any) -> bool:
    return isinstance(x, jex_core.Jaxpr)


def is_closed(x: Any) -> bool:
    return isinstance(x, jex_core.ClosedJaxpr)


def open_jaxpr(x: Any):
    """The underlying ``Jaxpr`` of a possibly-closed jaxpr."""
    return x.jaxpr if is_closed(x) else x


def subjaxprs(eqn) -> Iterator[tuple[str, int, Any]]:
    """Yield ``(param_key, index, open_jaxpr)`` for every jaxpr in the
    equation's params — the generic recursion the analyzer uses so new
    call-like primitives are walked without a per-primitive case."""
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for i, v in enumerate(vals):
            if is_jaxpr(v) or is_closed(v):
                yield key, i, open_jaxpr(v)


def all_primitives(jaxpr) -> frozenset:
    """Every primitive name reachable in a (closed) jaxpr, recursing
    through all sub-jaxprs via :func:`subjaxprs` — the coverage audit the
    serve-registry regression test pins: if a program emits a primitive the
    generic recursion cannot reach (a new call-like primitive whose jaxpr
    hides in an unprobed param), it will be missing here and the test
    snaps."""
    out: set = set()

    def walk(j):
        for eqn in open_jaxpr(j).eqns:
            out.add(eqn.primitive.name)
            for _key, _i, sub in subjaxprs(eqn):
                walk(sub)

    walk(open_jaxpr(jaxpr))
    return frozenset(out)


def norm_axes(axes: Any) -> tuple[str, ...]:
    """Collective axis params normalized to a tuple of NAMED axes (positional
    int axes from vmap land are not mesh axes and are dropped)."""
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list, frozenset, set)):
        return tuple(a for a in axes if isinstance(a, str))
    return (axes,) if isinstance(axes, str) else ()


def eqn_axes(eqn) -> tuple[str, ...]:
    """The named mesh axes a collective equation operates over."""
    p = eqn.params
    return norm_axes(p.get("axes", p.get("axis_name")))


def source_line(eqn) -> str:
    """User-source summary of an equation, '' when jax kept none."""
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return ""


def aval_bytes(aval) -> int:
    try:
        import numpy as np
        return int(aval.size) * int(np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0


def is_low_precision(dtype) -> bool:
    """True for dtypes that silently drop accumulation increments well
    before fp32 does (the dtype-drift rule's definition of '<fp32')."""
    import numpy as np
    try:
        d = np.dtype(dtype)
    except TypeError:
        d = np.dtype(getattr(dtype, "dtype", "float32"))
    if d.kind not in "fV":                 # ints/bools accumulate exactly
        return False
    name = getattr(dtype, "name", d.name)
    return name in ("bfloat16", "float16", "float8_e4m3fn", "float8_e5m2")


def trace_to_jaxpr(fn, *abstract_args, **abstract_kwargs):
    """``jax.make_jaxpr`` over abstract (ShapeDtypeStruct) or concrete args.

    This is the analyzer's only interaction with the function under test —
    zero FLOPs, no device buffers. Raises whatever tracing raises; callers
    that want trace errors AS findings use ``analyze()``'s wrapping.
    """
    return jax.make_jaxpr(fn)(*abstract_args, **abstract_kwargs)


def shape_dtype(x) -> jax.ShapeDtypeStruct:
    """Abstract stand-in for an array (device buffers stay untouched)."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    return jax.ShapeDtypeStruct(jax.numpy.shape(x), x.dtype)


def abstractify(tree):
    """Pytree of abstract stand-ins for a pytree of arrays."""
    return jax.tree.map(shape_dtype, tree)
