"""The vma helpers the shard_map engine shares (jax 0.9: ``jax.shard_map``,
``lax.pcast``, ``jax.typeof(...).vma``).

- :func:`shard_map` — ``jax.shard_map`` with ``check_vma=None`` meaning "jax's
  default" (vma checking on).
- :func:`vma_of` — the value's varying-manual-axes set.
- :func:`pvary_to` — the vma-anchor cast (``lax.pcast(..., to="varying")``)
  over exactly the axes the value does not already vary over.
- :func:`ct_like` — types a custom-vjp backward output to its primal.
- :func:`virtual_cpu_devices` — ``jax_num_cpu_devices`` for the CPU dry runs.
"""

from __future__ import annotations

import jax
from jax import lax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map``; ``check_vma=None`` leaves jax's default (on)."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def vma_of(x) -> frozenset:
    """The manual axes ``x`` is varying over."""
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def pvary_to(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """pcast ``x`` to varying over exactly the axes of ``axes`` it does not
    already vary over (pcast rejects mixed already/not-yet-varying sets).
    Value-wise the identity; a typing error from pcast propagates."""
    missing = tuple(a for a in axes if a not in vma_of(x))
    if not missing:
        return x
    return lax.pcast(x, missing, to="varying")


def ct_like(ct: jax.Array, primal: jax.Array) -> jax.Array:
    """Type a custom-vjp cotangent to its primal's varying axes: psum over
    the axes only the cotangent varies over (the reduction autodiff itself
    inserts when an invariant value met varying ones, e.g. the data-parallel
    gradient all-reduce of a replicated weight), pcast up the rest."""
    want = vma_of(primal)
    extra = tuple(sorted(vma_of(ct) - want))
    if extra:
        ct = lax.psum(ct, extra)
    return pvary_to(ct, tuple(sorted(want)))


def virtual_cpu_devices(n: int) -> None:
    """For the dry runs that are CPU runs by definition (``dryrun_multichip``,
    the analyzer CLI): ``n`` virtual CPU devices, asked for before the
    backend starts — the one place the package names a platform through the
    API. A backend that is already up is kept only if it is the CPU one with
    enough devices; any other (a live TPU backend included) is an error."""
    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        if jax.default_backend() != "cpu" or len(jax.devices()) < n:
            raise
