"""The lint passes: one walk of the traced step, five rule families.

The walker visits every equation of the traced step (recursing through
``scan``/``cond``/``pjit``/``shard_map``/``remat``/``custom_vjp`` sub-jaxprs)
carrying three pieces of state:

- a **replication environment** inside each ``shard_map``: for every value,
  the set of mesh axes it may VARY over (differ across devices). Inputs seed
  from ``in_specs``; ``axis_index`` introduces variance; ``psum``/
  ``all_gather`` over an axis remove it (every device then holds the same
  value); ``ppermute`` preserves it; control flow joins it (a ``switch`` on a
  stage index makes every branch output stage-varying). This is a static
  reimplementation of the vma/replication typing that ``check_rep=False``
  era shard_maps never got — and it is what catches a dropped gradient
  reduction (family ``unreduced-gradient``): a ``shard_map`` output whose
  ``out_specs`` CLAIM replication over an axis the dataflow says it still
  varies over means a ``psum``/``ring_psum``/reduce-scatter is missing
  before the optimizer update.

- a **provenance path** (which pjit/scan/cond frames enclose the eqn) plus
  jax's recorded source line, so findings point at code.

- a **trip multiplier** (product of enclosing scan lengths) for the
  bytes-over-ICI cost table.

The other families ride the same walk: ``ppermute-deadlock`` (non-bijective
permutations; collectives inside ``cond``/``switch`` branches that diverge —
the PR-2 XLA:CPU rendezvous caveat, now machine-checked — or inside ``while``
loops with device-varying trip counts), ``mesh-axis`` (axis names not in the
active mesh, permutation endpoints outside the axis), ``dtype-drift``
(sub-fp32 cross-device reductions and scan carries that accumulate in
sub-fp32), and ``donation`` (a buffer donated to a jitted call and read
again afterwards — the classic read-after-donate crash, caught before any
device allocates).
"""

from __future__ import annotations

from typing import Any

from simple_distributed_machine_learning_tpu.analysis.report import (
    CollectiveCost,
    Finding,
    Severity,
)
from simple_distributed_machine_learning_tpu.analysis.trace import (
    RENDEZVOUS_PRIMS,
    aval_bytes,
    eqn_axes,
    is_low_precision,
    norm_axes,
    open_jaxpr,
    prim_name,
    source_line,
    subjaxprs,
)

EMPTY: frozenset = frozenset()

# traffic factor over an axis group of n devices: bytes actually moved per
# operand byte by the standard ring algorithm for each collective kind
def _ici_factor(prim: str, n: int) -> float:
    if n <= 1:
        return 0.0
    return {
        "psum": 2.0 * (n - 1) / n,           # reduce-scatter + all-gather
        "pmin": 2.0 * (n - 1) / n,
        "pmax": 2.0 * (n - 1) / n,
        "all_gather": float(n - 1),           # (n-1) shards arrive
        "reduce_scatter": (n - 1) / n,
        "all_to_all": (n - 1) / n,            # keeps 1/n locally
        "ppermute": 1.0,                      # one hop, whole payload
        "pbroadcast": 1.0,
    }.get(prim, 1.0)


class _MeshCtx:
    """The active shard_map context: manual axis name -> size."""

    def __init__(self, axes: dict[str, int]):
        self.axes = dict(axes)

    def size(self, name: str) -> int | None:
        return self.axes.get(name)


def _mesh_axes_of(eqn, active_mesh) -> dict[str, int]:
    """Manual (non-auto) axes of a shard_map eqn, cross-checked against the
    launch mesh when one was passed to ``analyze``."""
    mesh = eqn.params.get("mesh", None)
    manual = eqn.params.get("manual_axes", None)
    axes: dict[str, int] = {}
    shape = getattr(mesh, "shape", None)
    if shape:
        for name, size in dict(shape).items():
            if manual is None or name in manual:
                axes[name] = int(size)
    if not axes and active_mesh is not None:
        axes = {n: int(s) for n, s in dict(active_mesh.shape).items()}
    return axes


def _spec_axes(spec: Any) -> frozenset:
    """A shard_map in_specs/out_specs entry (a ``PartitionSpec``: one of
    ``None`` / axis name / tuple of names per dim) as the flat set of mesh
    axes it maps."""
    out = set()
    for entry in tuple(spec or ()):
        if entry is not None:
            out.update(norm_axes(entry))
    return frozenset(out)


class Walker:
    """One pass over the traced step, accumulating findings and costs."""

    def __init__(self, active_mesh=None):
        self.active_mesh = active_mesh
        self.findings: list[Finding] = []
        self.costs: list[CollectiveCost] = []
        self._path: list[str] = []
        self._trips = 1
        self._mute = 0         # >0 during scan fixpoint pre-passes

    # -- plumbing ---------------------------------------------------------

    def _where(self, eqn=None) -> str:
        path = "/".join(self._path) or "<top>"
        src = source_line(eqn) if eqn is not None else ""
        return f"{path} ({src})" if src else path

    def _emit(self, rule: str, severity: Severity, message: str, eqn=None,
              hint: str = "") -> None:
        if self._mute:
            return
        self.findings.append(Finding(rule=rule, severity=severity,
                                     message=message, where=self._where(eqn),
                                     hint=hint))

    def _read(self, env: dict, atom) -> frozenset:
        # Literals (and unseen constvars) are device-uniform
        return env.get(id(atom), EMPTY) if hasattr(atom, "aval") else EMPTY

    # -- entry points -----------------------------------------------------

    def visit_outer(self, jaxpr, in_vary=None) -> list:
        """Walk a jaxpr OUTSIDE any shard_map: track donation, enter
        shard_maps, recurse through call-like eqns.

        ``in_vary`` optionally seeds DECLARED device-variance per invar (a
        caller's ``analysis.spec(..., vary=('data',))`` contract): a buffer
        whose shape is replicated but whose CONTENT each device holds a
        different shard of — exactly a ZeRO opt-state shard in the
        check_vma=False case, which no ``in_specs`` can express. The
        variance threads through call-like eqns into every shard_map's
        replication inference, where a consume-without-gather surfaces as a
        missing reduction (re-tagged ``sharded-state`` by run_rules).
        Returns the out-vars' variance (for the recursion)."""
        jaxpr = open_jaxpr(jaxpr)
        donated: dict[int, str] = {}       # id(var) -> donation site
        vary: dict[int, frozenset] = {}
        if in_vary:
            for var, v in zip(jaxpr.invars, in_vary):
                if v:
                    vary[id(var)] = frozenset(v)

        def _vary_of(atoms):
            return [vary.get(id(v), EMPTY) for v in atoms]

        for eqn in jaxpr.eqns:
            prim = prim_name(eqn)
            for invar in eqn.invars:
                key = id(invar)
                if key in donated:
                    self._emit(
                        "donation.read-after-donate", Severity.ERROR,
                        f"value donated at {donated[key]} is read again by "
                        f"'{prim}' — after donation the buffer may already "
                        f"be overwritten on device",
                        eqn,
                        hint="use the returned (updated) value, or drop the "
                             "argument from donate_argnums")
                    break
            if prim == "shard_map":
                self._path.append("shard_map")
                try:
                    self._visit_shard_map(eqn, incoming=_vary_of(eqn.invars))
                finally:
                    self._path.pop()
            elif prim in RENDEZVOUS_PRIMS or prim == "axis_index":
                # a mesh collective with no enclosing shard_map: axis names
                # can only bind through a mesh this analyzer cannot see
                self._emit(
                    "mesh-axis.unknown-axis", Severity.ERROR,
                    f"collective '{prim}' over {eqn_axes(eqn)} outside any "
                    f"shard_map — no mesh binds these axis names", eqn,
                    hint="collectives must run inside shard_map over a mesh "
                         "that names the axis")
            else:
                trips = (int(eqn.params.get("length", 1) or 1)
                         if prim == "scan" else 1)
                for key, _, sub in subjaxprs(eqn):
                    self._path.append(
                        f"pjit:{eqn.params.get('name', key)}"
                        if prim == "jit"
                        else f"scan[x{trips}]" if prim == "scan" else prim)
                    self._trips *= trips
                    try:
                        # map eqn invars onto this sub-jaxpr's params:
                        # cond branches drop the predicate, while's two
                        # jaxprs each see their own consts + the carry —
                        # declared vary= contracts must thread through
                        # these boundaries, not silently reset
                        if prim == "cond":
                            ev = list(eqn.invars)[1:]
                        elif prim == "while":
                            cnc = eqn.params.get("cond_nconsts", 0)
                            bnc = eqn.params.get("body_nconsts", 0)
                            iv = list(eqn.invars)
                            ev = (iv[:cnc] + iv[cnc + bnc:]
                                  if key == "cond_jaxpr" else iv[cnc:])
                        else:
                            ev = list(eqn.invars)
                        sub_vary = (_vary_of(ev)
                                    if len(sub.invars) == len(ev)
                                    else None)
                        outs = self.visit_outer(sub, in_vary=sub_vary)
                    finally:
                        self._trips //= trips
                        self._path.pop()
                    if (vary and key != "cond_jaxpr"
                            and len(outs) >= len(eqn.outvars)):
                        # union across sub-jaxprs (cond/switch branches):
                        # ANY branch's variance survives the join — the
                        # last branch overwriting would certify a defect
                        # reachable only through an earlier branch
                        for var, v in zip(eqn.outvars, outs):
                            if v:
                                vary[id(var)] = (
                                    vary.get(id(var), frozenset()) | v)
                if vary and not any(True for _ in subjaxprs(eqn)):
                    # plain eqn: declared variance flows through
                    union = frozenset().union(*_vary_of(eqn.invars)) \
                        if eqn.invars else EMPTY
                    if union:
                        for var in eqn.outvars:
                            vary[id(var)] = union
            if prim == "jit":
                don = eqn.params.get("donated_invars") or ()
                site = self._where(eqn)
                seen_at: dict[int, bool] = {}   # id(var) -> any donated
                flagged: set[int] = set()   # one finding per (eqn, buffer)
                for invar, d in zip(eqn.invars, don):
                    if not hasattr(invar, "aval"):
                        continue
                    key = id(invar)
                    if (key in seen_at and (d or seen_at[key])
                            and key not in flagged):
                        flagged.add(key)
                        self._emit(
                            "donation.double-donation", Severity.ERROR,
                            f"the same buffer is passed twice to "
                            f"'{eqn.params.get('name', 'pjit')}' with at "
                            f"least one position donated — the donated "
                            f"pages may be reused while the aliased "
                            f"parameter still reads them", eqn,
                            hint="pass distinct buffers, or drop the "
                                 "aliased position from donate_argnums")
                    seen_at[key] = seen_at.get(key, False) or bool(d)
                    if d:
                        donated[key] = site
        for outvar in jaxpr.outvars:
            if id(outvar) in donated:
                self._emit(
                    "donation.read-after-donate", Severity.ERROR,
                    f"value donated at {donated[id(outvar)]} is returned "
                    f"from the traced function — the caller would read a "
                    f"donated buffer", None,
                    hint="return the updated value instead of the donated "
                         "input")
        return _vary_of(jaxpr.outvars)

    def _visit_shard_map(self, eqn, incoming=None) -> None:
        axes = _mesh_axes_of(eqn, self.active_mesh)
        ctx = _MeshCtx(axes)
        inner = open_jaxpr(eqn.params["jaxpr"])
        in_vmas = [_spec_axes(sp) for sp in eqn.params["in_specs"]]
        if incoming:
            # declared content-variance (ZeRO shards in replicated-shape
            # buffers) joins whatever in_specs already map
            in_vmas = [v | inc for v, inc in
                       zip(in_vmas, incoming + [EMPTY] * len(in_vmas))]
        # cross-check the traced mesh against the launch mesh
        if self.active_mesh is not None:
            active = {n: int(s) for n, s in dict(self.active_mesh.shape).items()}
            for name, size in axes.items():
                if size > 1 and active.get(name, 1) != size:
                    self._emit(
                        "mesh-axis.mesh-mismatch", Severity.ERROR,
                        f"shard_map traced over mesh axis '{name}' of size "
                        f"{size}, but the active mesh has "
                        f"{name}={active.get(name, '<absent>')}", eqn,
                        hint="rebuild the step for the launch mesh (axis "
                             "sizes are baked in at trace time)")
        out_vmas = self._visit_vma(inner, in_vmas, ctx)
        for i, (spec, vma) in enumerate(zip(eqn.params["out_specs"],
                                            out_vmas)):
            claimed = _spec_axes(spec)
            missing = sorted(
                ax for ax in vma - claimed
                if ctx.size(ax) is not None and ctx.size(ax) > 1)
            if missing:
                aval = getattr(inner.outvars[i], "aval", None)
                shape = getattr(aval, "shape", "?")
                self._emit(
                    "unreduced-gradient.missing-reduce", Severity.ERROR,
                    f"shard_map output {i} (shape {shape}) still varies over "
                    f"mesh axis(es) {missing} but its out_spec claims "
                    f"replication — a cross-device reduction is missing on "
                    f"this path (each device would keep only its own "
                    f"partial value, e.g. an unsynced gradient)", eqn,
                    hint=f"psum/ring_psum/reduce-scatter over {missing} "
                         f"before returning, or map the axis in out_specs")

    # -- replication inference inside shard_map ---------------------------

    def _visit_vma(self, jaxpr, in_vmas, ctx) -> list:
        jaxpr = open_jaxpr(jaxpr)
        env: dict[int, frozenset] = {}
        for var in jaxpr.constvars:
            env[id(var)] = EMPTY
        for var, vma in zip(jaxpr.invars, in_vmas):
            env[id(var)] = frozenset(vma)
        for eqn in jaxpr.eqns:
            outs = self._eqn_vma(eqn, env, ctx)
            for var, vma in zip(eqn.outvars, outs):
                env[id(var)] = vma
        return [self._read(env, v) for v in jaxpr.outvars]

    def _eqn_vma(self, eqn, env, ctx) -> list:
        prim = prim_name(eqn)
        in_vmas = [self._read(env, v) for v in eqn.invars]
        union = frozenset().union(*in_vmas) if in_vmas else EMPTY
        n_out = len(eqn.outvars)

        if prim in RENDEZVOUS_PRIMS:
            return self._collective_vma(eqn, in_vmas, union, ctx)
        if prim == "axis_index":
            axes = eqn_axes(eqn)
            self._check_axes(eqn, axes, ctx)
            return [frozenset(axes)]
        if prim == "pvary":
            # pvary: the vma checker's varying cast — no wire traffic, the
            # value is simply typed (and from here on treated as) varying
            return [union | frozenset(eqn_axes(eqn))] * n_out
        if prim == "cond":
            return self._cond_vma(eqn, in_vmas, ctx)
        if prim == "scan":
            return self._scan_vma(eqn, in_vmas, ctx)
        if prim == "while":
            return self._while_vma(eqn, in_vmas, ctx)

        # generic call-like primitives (pjit, closed_call, remat2,
        # custom_jvp/vjp calls, ...): recurse when a sub-jaxpr's arity
        # matches, else fall back to the union rule
        for key, _, sub in subjaxprs(eqn):
            if len(sub.invars) == len(eqn.invars):
                self._path.append(prim if prim != "jit"
                                  else f"pjit:{eqn.params.get('name', '')}")
                try:
                    outs = self._visit_vma(sub, in_vmas, ctx)
                finally:
                    self._path.pop()
                if len(outs) >= n_out:
                    return outs[:n_out]
        return [union] * n_out

    def _collective_vma(self, eqn, in_vmas, union, ctx) -> list:
        prim = prim_name(eqn)
        axes = eqn_axes(eqn)
        self._check_axes(eqn, axes, ctx)
        self._check_dtype(eqn, prim)
        self._record_cost(eqn, prim, axes, ctx)
        groups = eqn.params.get("axis_index_groups")
        if prim == "ppermute":
            self._check_perm(eqn, axes, ctx)
            return [union] * len(eqn.outvars)
        if prim in ("psum", "pmin", "pmax", "all_gather"):
            if groups:
                # replicated only within each group: conservatively varying
                return [union] * len(eqn.outvars)
            return [vma - frozenset(axes) for vma in
                    (in_vmas if len(in_vmas) == len(eqn.outvars)
                     else [union] * len(eqn.outvars))]
        if prim in ("all_to_all", "reduce_scatter", "pbroadcast"):
            # device-dependent slices (or an explicit varying cast)
            return [union | frozenset(axes)] * len(eqn.outvars)
        return [union] * len(eqn.outvars)

    def _cond_vma(self, eqn, in_vmas, ctx) -> list:
        branches = eqn.params.get("branches") or ()
        pred_vma, op_vmas = in_vmas[0], in_vmas[1:]
        outs = None
        for b, branch in enumerate(branches):
            self._path.append(f"cond[branch {b}]")
            try:
                b_outs = self._visit_vma(branch, op_vmas, ctx)
            finally:
                self._path.pop()
            outs = (b_outs if outs is None else
                    [a | b_ for a, b_ in zip(outs, b_outs)])
        if outs is None:
            outs = [frozenset()] * len(eqn.outvars)
        self._check_branch_divergence(eqn, branches, pred_vma, ctx)
        return [o | pred_vma for o in outs]

    def _scan_vma(self, eqn, in_vmas, ctx) -> list:
        p = eqn.params
        body = p["jaxpr"]
        nc, ncar = p.get("num_consts", 0), p.get("num_carry", 0)
        length = int(p.get("length", 1) or 1)
        consts, carry = in_vmas[:nc], list(in_vmas[nc:nc + ncar])
        xs = in_vmas[nc + ncar:]
        self._check_carry_dtype(eqn, body, nc, ncar)
        # fixpoint on the carry (muted: no duplicate findings/costs)
        self._mute += 1
        try:
            for _ in range(len(ctx.axes) + 2):
                outs = self._visit_vma(body, consts + carry + xs, ctx)
                new_carry = [c | o for c, o in zip(carry, outs[:ncar])]
                if new_carry == carry:
                    break
                carry = new_carry
        finally:
            self._mute -= 1
        # final, reporting pass with the stabilized carry
        self._path.append(f"scan[x{length}]")
        self._trips *= length
        try:
            outs = self._visit_vma(body, consts + carry + xs, ctx)
        finally:
            self._trips //= length
            self._path.pop()
        return outs

    def _while_vma(self, eqn, in_vmas, ctx) -> list:
        p = eqn.params
        cnc, bnc = p.get("cond_nconsts", 0), p.get("body_nconsts", 0)
        cond_consts = in_vmas[:cnc]
        body_consts = in_vmas[cnc:cnc + bnc]
        carry = list(in_vmas[cnc + bnc:])
        pred_vma = EMPTY
        self._mute += 1
        try:
            for _ in range(len(ctx.axes) + 2):
                pred = self._visit_vma(p["cond_jaxpr"], cond_consts + carry,
                                       ctx)
                pred_vma = pred[0] if pred else EMPTY
                outs = self._visit_vma(p["body_jaxpr"], body_consts + carry,
                                       ctx)
                new_carry = [c | o | pred_vma for c, o in zip(carry, outs)]
                if new_carry == carry:
                    break
                carry = new_carry
        finally:
            self._mute -= 1
        if pred_vma and self._has_rendezvous(p["body_jaxpr"]):
            axes_used = self._rendezvous_axes(p["body_jaxpr"])
            sev = (Severity.ERROR if pred_vma & axes_used
                   else Severity.WARNING)
            self._emit(
                "ppermute-deadlock.varying-trip-count", sev,
                f"while loop whose trip count varies over {sorted(pred_vma)} "
                f"contains collectives over {sorted(axes_used)} — devices "
                f"would disagree on how many rendezvous to join", eqn,
                hint="make the trip count device-uniform (psum/pmax the "
                     "predicate) or hoist the collectives out of the loop")
        self._path.append("while")
        try:
            outs = self._visit_vma(p["body_jaxpr"], body_consts + carry, ctx)
        finally:
            self._path.pop()
        return [o | pred_vma for o in outs]

    # -- the individual checks -------------------------------------------

    def _check_axes(self, eqn, axes, ctx) -> None:
        known = set(ctx.axes)
        for ax in axes:
            if ax not in known:
                self._emit(
                    "mesh-axis.unknown-axis", Severity.ERROR,
                    f"collective '{eqn.primitive.name}' names axis '{ax}' "
                    f"which is not in the active mesh (axes: "
                    f"{sorted(known)})", eqn,
                    hint="fix the axis_name, or launch on a mesh that has "
                         "this axis")

    def _check_perm(self, eqn, axes, ctx) -> None:
        perm = eqn.params.get("perm")
        if perm is None or not axes:
            return
        size = 1
        for ax in axes:
            size *= ctx.size(ax) or 1
        pairs = [tuple(p) for p in perm]
        srcs = [s for s, _ in pairs]
        dsts = [d for _, d in pairs]
        oob = [i for i in srcs + dsts if not (0 <= i < size)]
        if oob:
            self._emit(
                "mesh-axis.perm-out-of-range", Severity.ERROR,
                f"ppermute over {axes} (size {size}) names device index(es) "
                f"{sorted(set(oob))} outside [0, {size})", eqn,
                hint="ring permutations must index devices of the named "
                     "axis; check the chunk/ring size against the mesh")
            return
        full = (len(pairs) == size and len(set(srcs)) == size
                and len(set(dsts)) == size)
        if not full:
            self._emit(
                "ppermute-deadlock.partial-perm", Severity.ERROR,
                f"ppermute over {axes} (size {size}) is not a full bijection "
                f"({len(set(srcs))} distinct sources, {len(set(dsts))} "
                f"distinct destinations, {size} needed) — devices outside "
                f"the permutation stall the collective-permute rendezvous "
                f"and receivers without a source read zeros", eqn,
                hint="send a (possibly dummy) chunk from every device: "
                     "perm=[(j, (j+1) % size) for j in range(size)]")

    def _check_dtype(self, eqn, prim) -> None:
        # min/max select an existing element — bf16 pmin/pmax are bit-exact;
        # only summing reductions lose increments below the ulp
        if prim not in ("psum", "reduce_scatter"):
            return
        for invar in eqn.invars:
            aval = getattr(invar, "aval", None)
            if aval is not None and is_low_precision(aval.dtype):
                self._emit(
                    "dtype-drift.low-precision-reduction", Severity.WARNING,
                    f"'{prim}' reduces {aval.dtype} operands across devices "
                    f"— cross-device accumulation in sub-fp32 loses "
                    f"increments as the axis (or value magnitude) grows",
                    eqn,
                    hint="accumulate in float32: cast before the reduction "
                         "and back after (the loss/grad paths already do)")
                return

    def _check_carry_dtype(self, eqn, body, nc, ncar) -> None:
        """Scan carries that ACCUMULATE (carry-out reachable from carry-in
        through an add) in sub-fp32: the classic silent drift — a bf16
        running sum stops growing once increments fall below its ulp."""
        body_j = open_jaxpr(body)
        carry_in = body_j.invars[nc:nc + ncar]
        carry_out = body_j.outvars[:ncar]
        for i, (vin, vout) in enumerate(zip(carry_in, carry_out)):
            aval = getattr(vin, "aval", None)
            if aval is None or not is_low_precision(aval.dtype):
                continue
            if self._accumulates(body_j, vin, vout):
                self._emit(
                    "dtype-drift.low-precision-carry", Severity.WARNING,
                    f"scan carry {i} accumulates in {aval.dtype}: a running "
                    f"sum in sub-fp32 silently drops increments (bf16 has 8 "
                    f"mantissa bits — sums stall near 256x the step size)",
                    eqn,
                    hint="carry the accumulator as float32 and cast at the "
                         "edges")

    @staticmethod
    def _accumulates(jaxpr, vin, vout) -> bool:
        """Is ``vout`` reachable from ``vin`` through an add-like eqn?"""
        add_like = {"add", "add_any", "scatter-add"}
        # taint[var] = (reachable, passed_through_add)
        taint: dict[int, bool] = {id(vin): False}
        for eqn in jaxpr.eqns:
            hit = [taint[id(v)] for v in eqn.invars if id(v) in taint]
            if not hit:
                continue
            via_add = any(hit) or prim_name(eqn) in add_like
            for ov in eqn.outvars:
                taint[id(ov)] = taint.get(id(ov), False) or via_add
            # recurse one level into call-like bodies cheaply: treat any
            # sub-jaxpr containing an add as an add on this path
            if not via_add:
                for _, _, sub in subjaxprs(eqn):
                    if any(prim_name(e) in add_like for e in sub.eqns):
                        for ov in eqn.outvars:
                            taint[id(ov)] = True
                        break
        return taint.get(id(vout), False)

    def _check_branch_divergence(self, eqn, branches, pred_vma, ctx) -> None:
        """Collectives inside cond/switch branches that do not line up
        across branches. If the predicate varies over the axis a collective
        runs over, devices in one rendezvous group take different branches —
        a hard deadlock everywhere. If it varies only over OTHER axes the
        groups are internally consistent (each group sees one branch), but
        backends with a global rendezvous (old XLA:CPU collective-permute —
        the PR-2 caveat) still deadlock: flag as a portability warning."""
        if not pred_vma or len(branches) < 2:
            return
        sigs = [self._collective_sig(b) for b in branches]
        axes_used: set = set()
        has_ppermute = False

        def scan_sig(sig):
            nonlocal has_ppermute
            for prim, axes, extra in sig:
                if prim == "scan":
                    scan_sig(extra)
                else:
                    axes_used.update(axes)
                    has_ppermute = has_ppermute or prim == "ppermute"
        for s in sigs:
            scan_sig(s)
        diverge = any(s != sigs[0] for s in sigs[1:])
        if diverge and pred_vma & axes_used:
            # devices of one rendezvous group take different branches and
            # issue different collective sequences: deadlock everywhere
            self._emit(
                "ppermute-deadlock.branch-divergent", Severity.ERROR,
                f"cond/switch on a predicate varying over "
                f"{sorted(pred_vma)} has branches with DIFFERENT collective "
                f"sequences over the SAME axes {sorted(pred_vma & axes_used)}"
                f" — devices of one collective group take different "
                f"branches: deadlock on every backend", eqn,
                hint="make every branch issue the same collective sequence "
                     "(dummy hops on non-participating branches)")
        elif has_ppermute:
            # the PR-2 caveat, machine-checked: ppermute rings inside
            # device-divergent branches are group-consistent (each stage's
            # seq/expert group agrees on its branch — safe on TPU, where the
            # permutes are independent ICI DMAs), but old XLA:CPU pairs
            # collective-permutes through one GLOBAL rendezvous, and the
            # stage-skewed branch execution deadlocks it. Branch-resident
            # psums/all-reduces rendezvous per group and are fine (TP
            # pipelines run green on CPU), so only rings are flagged.
            self._emit(
                "ppermute-deadlock.ring-in-branch", Severity.WARNING,
                f"ppermute ring(s) over {sorted(axes_used)} inside "
                f"cond/switch branches dispatched on a predicate varying "
                f"over {sorted(pred_vma)} — safe on TPU ICI, but old "
                f"XLA:CPU's global collective-permute rendezvous deadlocks "
                f"under branch-skewed execution (the PR-2 caveat)", eqn,
                hint="on CPU backends run this model on a 1-stage mesh (the "
                     "cli/tests fallback), or keep rings out of "
                     "stage-dispatched branches")
        elif diverge and not pred_vma & axes_used:
            # divergent psum/all-gather sequences with group-consistent
            # branch choice: correct and deadlock-free (per-group
            # rendezvous); surface as INFO so audits still see it
            self._emit(
                "ppermute-deadlock.branch-divergent", Severity.INFO,
                f"cond/switch branches issue different (non-ppermute) "
                f"collective sequences over {sorted(axes_used)}; the "
                f"predicate varies only over {sorted(pred_vma)}, so each "
                f"collective group agrees on its branch — correct, noted "
                f"for audit", eqn)

    def _collective_sig(self, jaxpr) -> tuple:
        """Ordered sequence of rendezvous collectives a branch issues
        (recursively; scans contribute their body times the trip count —
        encoded structurally so differing lengths differ)."""
        sig = []
        for eqn in open_jaxpr(jaxpr).eqns:
            prim = prim_name(eqn)
            if prim in RENDEZVOUS_PRIMS:
                perm = eqn.params.get("perm")
                sig.append((prim, eqn_axes(eqn),
                            tuple(map(tuple, perm)) if perm else None))
            elif prim == "scan":
                inner = self._collective_sig(eqn.params["jaxpr"])
                if inner:
                    sig.append(("scan", (int(eqn.params.get("length", 1) or 1),),
                                inner))
            else:
                for _, _, sub in subjaxprs(eqn):
                    sig.extend(self._collective_sig(sub))
        return tuple(sig)

    def _has_rendezvous(self, jaxpr) -> bool:
        return bool(self._collective_sig(jaxpr))

    def _rendezvous_axes(self, jaxpr) -> frozenset:
        axes = set()

        def collect(sig):
            for prim, a, extra in sig:
                if prim == "scan":
                    collect(extra)
                else:
                    axes.update(a)
        collect(self._collective_sig(jaxpr))
        return frozenset(axes)

    def _record_cost(self, eqn, prim, axes, ctx) -> None:
        if self._mute or prim not in RENDEZVOUS_PRIMS:
            return
        group = 1
        for ax in axes:
            group *= ctx.size(ax) or 1
        payload = sum(aval_bytes(getattr(v, "aval", None)) or 0
                      for v in eqn.invars
                      if getattr(v, "aval", None) is not None)
        self.costs.append(CollectiveCost(
            prim=prim, axes=tuple(axes), group_size=group,
            bytes_per_call=payload,
            ici_bytes=int(payload * _ici_factor(prim, group)),
            trips=self._trips, where=self._where(eqn)))


def run_rules(closed_jaxpr, active_mesh=None, arg_ranges=None, arg_vary=None):
    """Run every lint pass over a traced step; returns (findings, costs).

    ``arg_ranges``/``arg_vary`` are flat per-invar contract annotations
    (from ``analysis.spec`` args, see ``analyze``): value intervals engage
    the scatter-bounds interval pass; declared device-variance engages the
    sharded-state pass — the replication inference runs twice, and a
    missing-reduction finding present ONLY under the declared shards is
    re-tagged ``sharded-state.missing-gather`` (the defect is consuming a
    sharded buffer without gathering it, not a dropped gradient psum).
    """
    import dataclasses

    w = Walker(active_mesh=active_mesh)
    w.visit_outer(closed_jaxpr, in_vary=arg_vary)
    findings, costs = w.findings, w.costs

    if arg_vary and any(arg_vary):
        base = Walker(active_mesh=active_mesh)
        base.visit_outer(closed_jaxpr)
        base_keys = {(f.rule, f.where) for f in base.findings}
        retagged = []
        for f in findings:
            if (f.rule == "unreduced-gradient.missing-reduce"
                    and (f.rule, f.where) not in base_keys):
                f = dataclasses.replace(
                    f, rule="sharded-state.missing-gather",
                    message=("a buffer DECLARED device-sharded (a ZeRO "
                             "param/opt-state shard in a replicated-shape "
                             "buffer) flows into this output without a "
                             "gather/reduce: " + f.message),
                    hint="all_gather the shard (or psum the partial) over "
                         "the declared axis before it meets replicated "
                         "state — gather-before-use / reduce-before-update")
            retagged.append(f)
        findings = retagged

    # The bounds pass always runs — even with no declared contracts, a
    # PROMISE_IN_BOUNDS gather/scatter must surface as unproven-promise
    # rather than analyze vacuously clean (an empty report is a proof).
    from simple_distributed_machine_learning_tpu.analysis.bounds import (
        check_bounds,
    )
    findings = findings + check_bounds(closed_jaxpr, list(arg_ranges or ()))
    return findings, costs
