"""Host-side AST lint: the ``_DECODE_BUILD_CACHE`` discipline.

The jaxpr rules see compiled programs; this pass sees the PYTHON that
builds them. The discipline (models/serving.py, PR 6): every decode-path
builder is memoized on its static config in ``_DECODE_BUILD_CACHE``, so a
fleet of engines (and a test suite full of them) shares one traced +
compiled program per config. Three ways the discipline rots, all cheap to
catch with ``ast`` and expensive to catch in production:

- ``hostlint.unmemoized-builder`` — a decode builder in ``models/gpt.py``
  whose body no longer routes through ``memo_build`` (a refactor dropped
  the memo; every engine recompiles);
- ``hostlint.builder-bypass`` — a call site anywhere outside
  ``models/gpt.py`` invoking a private ``_build_*`` helper directly,
  skipping the memo the public ``make_*`` wraps around it;
- ``hostlint.cache-poke`` — code outside ``models/serving.py`` and ``gpt.py``
  touching ``_DECODE_BUILD_CACHE`` itself (clearing or seeding it from afar);
- ``hostlint.raw-jit-in-serve`` — a ``jax.jit`` created inside ``serve/``:
  the serving layer's contract is that every compiled program comes from
  the memoized gpt builders, so a stray jit there is an unmemoized program
  by construction;
- ``hostlint.wall-clock-in-serve`` — a wall-clock or RNG CALL inside
  ``serve/`` (``time.time``/``monotonic``/``perf_counter``,
  ``datetime.now``, ``random.*``): the exact-pinned scenario suite and the
  journal-replay determinism contract (PRs 10-11) hold ONLY because every
  clock read goes through the injectable plumbing (``clock=`` default
  args, the simulator's VirtualClock) — referencing ``time.monotonic`` as
  a default is sanctioned, calling it inline is not;
- ``metric-catalog.undocumented`` — a metric name registered in
  ``serve/metrics.py`` or the telemetry SLO/attribution modules (any
  full-string constant matching the ``serve_*``/``train_*`` metric
  grammar) that ``telemetry/catalog.py`` cannot resolve to a HELP bullet:
  an instrument with no documentation renders ``HELP <name> (undocumented)``
  in the Prometheus exposition and tells an operator nothing. The catalog
  module is loaded by file path (it imports only ast/os/re), so this rule
  — like every other hostlint rule — runs without jax;
- ``journal-grammar.unread-event`` — a journal event kind some writer in
  ``serve/`` emits (a dict display with a constant ``"ev"`` key) that NO
  reader dispatches on: neither ``serve/journal.py::recover_state`` (the
  crash-recovery fold) nor the telemetry report reader compares the
  ``"ev"`` field against it. A record type nobody reads silently vanishes
  on recovery — the exact failure mode the protocol model checker
  (analysis/protocol.py) assumes away, so the grammar cross-check is what
  keeps the abstraction honest against the real writers.

Pure ``ast`` — no jax import, so the CI lint job runs it in milliseconds:
``python -m simple_distributed_machine_learning_tpu.analysis --hostlint``.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import re

from simple_distributed_machine_learning_tpu.analysis.report import (
    Finding,
    Report,
    Severity,
)

# The memoized decode-path builders (mirrors models.gpt.DECODE_BUILDERS —
# tests/test_analysis_serve.py pins the two lists equal so this cannot
# silently drift from the real module).
DECODE_BUILDER_NAMES = (
    "make_cached_decoder",
    "make_slot_prefill",
    "make_paged_prefill_chunk",
    "make_paged_decode_step",
    "make_paged_block_copy",
    "make_paged_block_write",
    "make_adapter_bank_update",
    "make_slot_propose",
    "make_paged_verify_step",
    "make_paged_spec_tick",
)

_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO = os.path.dirname(_PKG)
GPT_PATH = os.path.join(_PKG, "models", "gpt.py")


def _calls_in(node) -> list:
    return [n for n in ast.walk(node) if isinstance(n, ast.Call)]


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _jit_bindings(tree) -> tuple[set, set]:
    """Names a module binds to jax itself and to jit-like callables, so
    every spelling is caught: ``jax.jit``, ``import jax as j; j.jit``,
    ``from jax import jit [as q]``, ``from jax.experimental.pjit import
    pjit``."""
    jax_aliases, jit_names = {"jax"}, set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax":
                    jax_aliases.add(a.asname or "jax")
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for a in node.names:
                    if a.name in ("jit", "pjit"):
                        jit_names.add(a.asname or a.name)
            elif node.module and node.module.startswith("jax."):
                for a in node.names:
                    if a.name == "pjit":
                        jit_names.add(a.asname or "pjit")
    return jax_aliases, jit_names


#: wall-clock readers in the ``time`` module (sleep excluded: it consumes
#: time rather than reads it, and the simulator injects it explicitly)
_WALLCLOCK_TIME_FNS = ("time", "monotonic", "perf_counter", "time_ns",
                       "monotonic_ns", "perf_counter_ns")
_WALLCLOCK_DT_FNS = ("now", "utcnow", "today")


def _clock_bindings(tree) -> tuple[set, set, set, set]:
    """Names a module binds to the time/datetime/random modules and to
    wall-clock functions imported from them, mirroring ``_jit_bindings``'s
    alias resolution so every spelling is caught."""
    time_a, dt_a, rand_a, direct = set(), set(), set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "time":
                    time_a.add(a.asname or "time")
                elif a.name == "datetime":
                    dt_a.add(a.asname or "datetime")
                elif a.name == "random":
                    rand_a.add(a.asname or "random")
        elif isinstance(node, ast.ImportFrom):
            if node.module == "time":
                for a in node.names:
                    if a.name in _WALLCLOCK_TIME_FNS:
                        direct.add(a.asname or a.name)
            elif node.module == "datetime":
                for a in node.names:
                    if a.name in ("datetime", "date"):
                        dt_a.add(a.asname or a.name)
            elif node.module == "random":
                for a in node.names:
                    direct.add(a.asname or a.name)
    return time_a, dt_a, rand_a, direct


def _wallclock_call(call: ast.Call, bindings) -> str | None:
    """The dotted name of a wall-clock/RNG read this Call performs, or
    None. Only CALLS count — ``clock=time.monotonic`` default-arg
    REFERENCES are the sanctioned injection points."""
    time_a, dt_a, rand_a, direct = bindings
    f = call.func
    if isinstance(f, ast.Name) and f.id in direct:
        return f.id
    if isinstance(f, ast.Attribute):
        root = f.value
        if isinstance(root, ast.Name):
            if root.id in time_a and f.attr in _WALLCLOCK_TIME_FNS:
                return f"{root.id}.{f.attr}"
            if root.id in rand_a:
                return f"{root.id}.{f.attr}"
            if root.id in dt_a and f.attr in _WALLCLOCK_DT_FNS:
                return f"{root.id}.{f.attr}"
        if (isinstance(root, ast.Attribute)
                and isinstance(root.value, ast.Name)
                and root.value.id in dt_a
                and f.attr in _WALLCLOCK_DT_FNS):
            return f"{root.value.id}.{root.attr}.{f.attr}"
    return None


def _is_jax_jit(node, jax_aliases: set, jit_names: set) -> bool:
    """A jit reference in any spelling (covers ``jax.jit(...)``,
    ``@jax.jit``, ``functools.partial(jax.jit, ...)`` operands, and the
    aliased forms ``_jit_bindings`` resolves)."""
    if (isinstance(node, ast.Attribute) and node.attr in ("jit", "pjit")
            and isinstance(node.value, ast.Name)
            and node.value.id in jax_aliases):
        return True
    return isinstance(node, ast.Name) and node.id in jit_names


def _where(path: str, node, repo: str = _REPO) -> str:
    rel = os.path.relpath(path, repo)
    return f"{rel}:{getattr(node, 'lineno', '?')}"


def lint_builder_definitions(gpt_path: str = GPT_PATH) -> list[Finding]:
    """Every decode builder's definition must route through the memo."""
    with open(gpt_path) as f:
        tree = ast.parse(f.read(), filename=gpt_path)
    findings: list[Finding] = []
    defs = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
    for name in DECODE_BUILDER_NAMES:
        fn = defs.get(name)
        if fn is None:
            findings.append(Finding(
                rule="hostlint.unmemoized-builder", severity=Severity.ERROR,
                message=f"decode builder '{name}' not found in "
                        f"{os.path.basename(gpt_path)} — the hostlint "
                        f"builder list is stale or the builder was removed",
                where=_where(gpt_path, tree),
                hint="update DECODE_BUILDER_NAMES alongside the builder"))
            continue
        if not any(_call_name(c) == "memo_build" for c in _calls_in(fn)):
            findings.append(Finding(
                rule="hostlint.unmemoized-builder", severity=Severity.ERROR,
                message=(f"decode builder '{name}' no longer routes its "
                         f"build through memo_build — every engine and "
                         f"test constructing it re-traces and re-compiles "
                         f"an identical program"),
                where=_where(gpt_path, fn),
                hint="wrap the build in memo_build(key, build) keyed on "
                     "the static config (see the sibling builders)"))
    return findings


def _lint_call_sites(path: str, allow_jit: bool,
                     repo: str = _REPO,
                     check_clock: bool | None = None) -> list[Finding]:
    # historically the wall-clock rule rode on the serve/ (allow_jit)
    # gate; check_clock decouples them so determinism-pinned modules
    # OUTSIDE serve/ (the telemetry SLO pipeline) get clock-checked
    # without inheriting the raw-jit rule
    if check_clock is None:
        check_clock = not allow_jit
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    findings: list[Finding] = []
    jax_aliases, jit_names = _jit_bindings(tree)
    clock_bindings = _clock_bindings(tree)
    for node in ast.walk(tree):
        if (isinstance(node, (ast.Name, ast.Attribute))
                and (node.id if isinstance(node, ast.Name) else node.attr)
                == "_DECODE_BUILD_CACHE"):
            findings.append(Finding(
                rule="hostlint.cache-poke", severity=Severity.ERROR,
                message="_DECODE_BUILD_CACHE touched outside "
                        "models/serving.py — the memo's invariants (keying, "
                        "shared executables) belong to its owner",
                where=_where(path, node, repo),
                hint="go through the public make_* builders"))
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name.startswith("_build_") and any(
                    name == "_build" + pub[len("make"):]
                    for pub in DECODE_BUILDER_NAMES):
                findings.append(Finding(
                    rule="hostlint.builder-bypass", severity=Severity.ERROR,
                    message=(f"direct call to private builder '{name}' "
                             f"skips the _DECODE_BUILD_CACHE memo — this "
                             f"call site compiles its own copy of the "
                             f"program"),
                    where=_where(path, node, repo),
                    hint=f"call the public "
                         f"make{name[len('_build'):]} instead"))
            if check_clock:
                clock = _wallclock_call(node, clock_bindings)
                if clock is not None:
                    findings.append(Finding(
                        rule="hostlint.wall-clock-in-serve",
                        severity=Severity.ERROR,
                        message=(f"'{clock}()' called inside a "
                                 f"determinism-pinned module (serve/ and "
                                 f"the telemetry SLO pipeline) — the "
                                 f"exact-pinned scenarios and journal "
                                 f"replay are deterministic ONLY because "
                                 f"every clock/RNG read goes through the "
                                 f"injectable plumbing"),
                        where=_where(path, node, repo),
                        hint="take the clock as an injectable default arg "
                             "(clock=time.monotonic) or use the "
                             "simulator's VirtualClock; seed randomness "
                             "explicitly"))
        if not allow_jit and _is_jax_jit(node, jax_aliases, jit_names):
            findings.append(Finding(
                rule="hostlint.raw-jit-in-serve", severity=Severity.ERROR,
                message="jax.jit created inside serve/ — serving programs "
                        "must come from the memoized models/gpt.py "
                        "builders, or every engine compiles its own",
                where=_where(path, node, repo),
                hint="add (or extend) a memoized make_* builder in "
                     "models/gpt.py and call that"))
    return findings


JOURNAL_PATH = os.path.join(_PKG, "serve", "journal.py")
TELEMETRY_REPORT_PATH = os.path.join(_PKG, "telemetry", "report.py")


def _is_ev_load(expr) -> bool:
    """``<x>["ev"]`` or ``<x>.get("ev", ...)`` — the two spellings the
    journal readers use to pull a record's event kind. Keyed on the
    literal ``"ev"`` so ``r.get("kind")`` dispatches (metrics records)
    never count as journal reads."""
    if (isinstance(expr, ast.Subscript)
            and isinstance(expr.slice, ast.Constant)
            and expr.slice.value == "ev"):
        return True
    return (isinstance(expr, ast.Call)
            and isinstance(expr.func, ast.Attribute)
            and expr.func.attr == "get" and expr.args
            and isinstance(expr.args[0], ast.Constant)
            and expr.args[0].value == "ev")


def _event_writes(path: str, repo: str = _REPO) -> list:
    """``(kind, where)`` for every journal record literal in a module: a
    dict display carrying a constant ``"ev"`` key with a constant string
    value — the shape every ``RequestJournal.log_*`` writer uses."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict):
            continue
        for k, v in zip(node.keys, node.values):
            if (isinstance(k, ast.Constant) and k.value == "ev"
                    and isinstance(v, ast.Constant)
                    and isinstance(v.value, str)):
                out.append((v.value, _where(path, node, repo)))
    return out


def _event_reads(path: str) -> set:
    """Every event kind a reader module dispatches on: string constants
    compared (``==`` or ``in (...)``) against a value that came from the
    ``"ev"`` key — directly (``ev.get("ev") == "restart"``) or through a
    variable (``kind = ev["ev"]; ... kind == "submit"``)."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    kind_vars: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _is_ev_load(node.value):
            kind_vars.update(t.id for t in node.targets
                             if isinstance(t, ast.Name))
    kinds: set = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare) or len(node.ops) != 1:
            continue
        if not (_is_ev_load(node.left)
                or (isinstance(node.left, ast.Name)
                    and node.left.id in kind_vars)):
            continue
        comp = node.comparators[0]
        if (isinstance(node.ops[0], ast.Eq)
                and isinstance(comp, ast.Constant)
                and isinstance(comp.value, str)):
            kinds.add(comp.value)
        elif (isinstance(node.ops[0], ast.In)
                and isinstance(comp, (ast.Tuple, ast.List, ast.Set))):
            kinds.update(e.value for e in comp.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str))
    return kinds


def lint_journal_grammar(writer_paths=None, reader_paths=None,
                         repo: str = _REPO) -> list[Finding]:
    """The writer/reader cross-check: every event kind any ``serve/``
    writer emits must have a dispatching reader in ``recover_state`` or
    the telemetry report — AST-checked, so a new record type can never
    silently vanish on recovery. Paths are parameterizable so the tests
    can lint seeded-defect modules."""
    if writer_paths is None:
        serve_dir = os.path.join(_PKG, "serve")
        writer_paths = [os.path.join(serve_dir, f)
                        for f in sorted(os.listdir(serve_dir))
                        if f.endswith(".py")]
    if reader_paths is None:
        reader_paths = [JOURNAL_PATH, TELEMETRY_REPORT_PATH]
    read: set = set()
    for p in reader_paths:
        read |= _event_reads(p)
    findings: list[Finding] = []
    for p in writer_paths:
        for kind, where in _event_writes(p, repo):
            if kind not in read:
                findings.append(Finding(
                    rule="journal-grammar.unread-event",
                    severity=Severity.ERROR,
                    message=(f"journal event kind '{kind}' is written "
                             f"here but NO reader dispatches on it — "
                             f"neither recover_state nor the telemetry "
                             f"report compares the 'ev' field against "
                             f"'{kind}', so the record silently vanishes "
                             f"on recovery/replay"),
                    where=where,
                    hint="add a recover_state branch (or a report reader) "
                         "for the new kind, and a transition for it in "
                         "the protocol model (analysis/protocol.py)"))
    return findings


#: modules whose full-string ``serve_*``/``train_*`` constants ARE metric
#: names (verified by inspection — no span names or jsonl kinds match the
#: grammar here); the catalog rule scans exactly these.
_METRIC_FILES = (("serve", "metrics.py"), ("telemetry", "slo.py"),
                 ("telemetry", "attribution.py"))
_METRIC_NAME_RE = re.compile(r"^(serve|train)_[a-z0-9_]+$")


def _metric_constants(path: str) -> list[tuple]:
    """``(name, node)`` for every full-string constant in ``path`` that
    matches the metric-name grammar."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    return [(node.value, node) for node in ast.walk(tree)
            if isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and _METRIC_NAME_RE.match(node.value)]


def lint_metric_catalog(metric_files=None,
                        repo: str = _REPO) -> list[Finding]:
    """``metric-catalog.undocumented``: every metric name that appears in
    the registering modules must resolve through
    ``telemetry/catalog.py::metric_help`` (a HELP bullet in a catalog
    docstring or an ``EXTRA_HELP`` entry). The catalog module is loaded by
    FILE PATH — importing the ``telemetry`` package would pull in jax,
    and the CI lint job (and ``test_hostlint_runs_without_jax``) run this
    suite on a jax-free interpreter. ``metric_files`` parameterizes the
    scanned modules for seeded-defect tests, mirroring
    ``lint_journal_grammar``'s writer/reader path injection."""
    pkg = os.path.join(repo, "simple_distributed_machine_learning_tpu")
    catalog_path = os.path.join(pkg, "telemetry", "catalog.py")
    spec = importlib.util.spec_from_file_location(
        "_sdml_hostlint_catalog", catalog_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    help_map = mod.metric_help()
    if metric_files is None:
        metric_files = [os.path.join(pkg, *rel) for rel in _METRIC_FILES]
    findings: list[Finding] = []
    for path in metric_files:
        for name, node in _metric_constants(path):
            if name not in help_map:
                findings.append(Finding(
                    rule="metric-catalog.undocumented",
                    severity=Severity.ERROR,
                    message=(f"metric '{name}' is registered but "
                             f"telemetry/catalog.py has no HELP text for "
                             f"it — the Prometheus exposition renders "
                             f"'(undocumented)' and operators fly blind"),
                    where=_where(path, node, repo),
                    hint="add a ``{name}`` — help bullet to the owning "
                         "module's docstring (catalog.py parses the "
                         "bullet grammar) or an EXTRA_HELP entry"))
    return findings


def lint_repo(repo: str = _REPO) -> Report:
    """The whole hostlint suite: builder definitions in models/gpt.py;
    cache-poke and builder-bypass EVERYWHERE outside the memo's home
    (models/serving.py) and the builders' (models/gpt.py) — the whole
    package, repo-root scripts (bench.py) and tests/ — because a poke from
    cli.py or bench.py rots the memo just as surely as one from serve/;
    raw-jit additionally in serve/ (every other layer creates jits
    legitimately)."""
    pkg = os.path.join(repo,
                       "simple_distributed_machine_learning_tpu")
    gpt = os.path.abspath(os.path.join(pkg, "models", "gpt.py"))
    owners = (gpt, os.path.abspath(os.path.join(pkg, "models", "serving.py")))
    findings = lint_builder_definitions(gpt)
    findings.extend(lint_journal_grammar(repo=repo))
    findings.extend(lint_metric_catalog(repo=repo))
    serve_dir = os.path.abspath(os.path.join(pkg, "serve")) + os.sep
    # determinism-pinned modules outside serve/: the SLO/alert/attribution
    # pipeline feeds exact-pinned scenario numbers, so it gets the same
    # no-wall-clock rule (without serve/'s raw-jit rule)
    clock_paths = {os.path.abspath(os.path.join(pkg, "telemetry", f))
                   for f in ("slo.py", "alerts.py", "attribution.py")}
    paths: list[str] = []
    for d in (pkg, os.path.join(repo, "tests")):
        if not os.path.isdir(d):
            continue
        for root, _dirs, files in sorted(os.walk(d)):
            for fname in sorted(files):
                if fname.endswith(".py"):
                    paths.append(os.path.join(root, fname))
    paths.extend(os.path.join(repo, f) for f in sorted(os.listdir(repo))
                 if f.endswith(".py"))
    for path in paths:
        ap = os.path.abspath(path)
        if ap in owners:
            continue
        findings.extend(_lint_call_sites(
            path, allow_jit=not ap.startswith(serve_dir), repo=repo,
            check_clock=(ap.startswith(serve_dir) or ap in clock_paths)))
    return Report(name="hostlint", findings=findings)
