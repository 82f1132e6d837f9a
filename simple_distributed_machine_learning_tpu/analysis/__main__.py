"""CLI for the static analyzer — the standalone preflight gate.

Examples::

    # lint the exact steps dryrun_multichip(8) executes (CI runs 1..10)
    python -m simple_distributed_machine_learning_tpu.analysis --dryrun 8

    # run one seeded-defect fixture (exits non-zero when it flags, which a
    # defect fixture always must)
    python -m simple_distributed_machine_learning_tpu.analysis \
        --fixture dropped_grad_sync

    # self-test every fixture against its contract (defects flag, cleans
    # pass) — the CI lint job's other half
    python -m simple_distributed_machine_learning_tpu.analysis --fixtures

Exit code: 0 when every analyzed step satisfies ``--fail-on`` (default:
``warning`` for fixtures — a demonstration must demonstrate — and ``error``
for ``--dryrun``/preflights, where e.g. a deliberate-bf16 dtype warning must
not block a launch).
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m simple_distributed_machine_learning_tpu.analysis",
        description="static sharding & collective analyzer (preflight gate)")
    p.add_argument("--dryrun", type=int, default=None, metavar="N",
                   help="analyze the steps dryrun_multichip(N) executes on "
                        "an N-virtual-device mesh")
    p.add_argument("--serve", action="store_true",
                   help="lint the serving-program registry (cached decoder "
                        "+ paged prefill chunk, decode, CoW copy and the "
                        "composite tick) at two block/chunk shapes, under "
                        "the fused kernel, with adapters, the speculative "
                        "programs and the serve supervisor's "
                        "degraded-fallback layout")
    p.add_argument("--serve-kernel", action="store_true",
                   help="kernel-only preflight over the same registry "
                        "sweep: every layout's Pallas kernel paths must "
                        "lint clean — zero kernel-family findings at ANY "
                        "severity (no unproven index maps), zero trace "
                        "failures (the gate ROADMAP #2's autotuner runs "
                        "every candidate through)")
    p.add_argument("--hostlint", action="store_true",
                   help="host-side AST lint: decode builders memoized "
                        "through _DECODE_BUILD_CACHE, no bypass call "
                        "sites in serve/ or tests/, no raw jax.jit in "
                        "serve/, journal writer/reader grammar "
                        "cross-check (pure ast, no tracing)")
    p.add_argument("--serve-protocol", action="store_true",
                   help="bounded model checking of the serve fleet "
                        "protocol: exhaustively explore every "
                        "tick/crash/handoff/adopt/shed/prefetch/retire "
                        "interleaving of an abstract 2-pool fleet to "
                        "--depth and prove the no-double-serve / "
                        "no-lost-request / refcount-conservation / "
                        "boarding-gate invariants (pure stdlib, no jax; "
                        "exit 2 on a violated invariant, each violation "
                        "prints its counterexample + exported chaos "
                        "schedule)")
    p.add_argument("--depth", type=int, default=None, metavar="N",
                   help="--serve-protocol exploration depth bound "
                        "(default: the clean model's pinned depth 8)")
    p.add_argument("--fixture", default=None, metavar="NAME",
                   help="run one seeded fixture (see --list)")
    p.add_argument("--fixtures", action="store_true",
                   help="self-test every fixture against its contract")
    p.add_argument("--list", action="store_true",
                   help="list fixtures and rule families")
    p.add_argument("--fail-on", choices=("error", "warning"), default=None,
                   help="finding severity that makes the exit code non-zero "
                        "(default: warning for fixtures, error for --dryrun)")
    p.add_argument("--costs", action="store_true",
                   help="print the bytes-over-ICI cost table per step")
    args = p.parse_args(argv)

    if args.list:
        from simple_distributed_machine_learning_tpu.analysis.fixtures import (
            FIXTURES,
        )
        print("rule families: ppermute-deadlock unreduced-gradient "
              "mesh-axis dtype-drift donation scatter-bounds "
              "retrace-explosion sharded-state hostlint journal-grammar "
              "protocol kernel-oob kernel-unproven kernel-race "
              "kernel-tile kernel-dtype-drift kernel-hbm")
        print("fixtures:")
        for fx in FIXTURES.values():
            kind = "defect" if fx.defect else "clean"
            print(f"  {fx.name:<24} [{kind:>6}] {fx.description}")
        return 0

    if not (args.hostlint or args.serve or args.serve_kernel or args.fixtures
            or args.serve_protocol or args.fixture is not None
            or args.dryrun is not None):
        p.error("nothing to do: pass --dryrun N, --serve, --serve-kernel, "
                "--hostlint, --serve-protocol, --fixture NAME, --fixtures "
                "or --list")
    if args.dryrun is not None and args.dryrun < 1:
        p.error(f"--dryrun needs a positive device count, got "
                f"{args.dryrun}")

    # Modes compose: every requested mode runs and the exit code ANDs the
    # results (a combined `--serve --hostlint` must not silently drop one
    # gate).  Bootstrap once, sized for the most demanding requested mode —
    # --hostlint and --serve-protocol alone stay jax-free (pure ast /
    # pure stdlib; pinned by a purge-and-block subprocess test).
    need = max(1 if (args.serve or args.serve_kernel) else 0,
               8 if (args.fixtures or args.fixture is not None) else 0,
               args.dryrun or 0)
    if need:
        # the analyzer traces on virtual CPU devices (no FLOPs run); imported
        # here so --serve-protocol stays jax-free
        from simple_distributed_machine_learning_tpu.parallel.compat import (
            virtual_cpu_devices,
        )
        virtual_cpu_devices(need)
    ok = True
    protocol_violated = False

    if args.hostlint:
        import os as _os

        from simple_distributed_machine_learning_tpu.analysis.hostlint import (
            lint_repo,
        )
        report = lint_repo()
        # the SDML_LINT_INJECT gate drill, mirrored inline (importing
        # programs.py's helper would pull jax into this jax-free mode)
        tag = _os.environ.get("SDML_LINT_INJECT")
        if tag:
            from simple_distributed_machine_learning_tpu.analysis.report import (  # noqa: E501
                Finding,
                Severity,
            )
            report.findings.append(Finding(
                rule=f"injected.{tag}", severity=Severity.ERROR,
                message="seeded ERROR finding injected via "
                        "SDML_LINT_INJECT — the gate drill proving "
                        "--lint preflights actually fail",
                where="SDML_LINT_INJECT", hint="unset SDML_LINT_INJECT"))
        print(report.format(costs=False))
        host_ok = report.ok(args.fail_on or "error")
        print(f"analysis --hostlint: {'clean' if host_ok else 'FLAGGED'}")
        ok &= host_ok

    if args.serve:
        from simple_distributed_machine_learning_tpu.analysis.programs import (
            default_registry_reports,
        )
        reports = default_registry_reports()
        for r in reports:
            print(r.format(costs=args.costs))
        fail_on = args.fail_on or "error"
        serve_ok = all(r.ok(fail_on) for r in reports)
        print(f"analysis --serve: {len(reports)} layouts "
              f"{'clean' if serve_ok else 'FLAGGED'}")
        ok &= serve_ok

    if args.serve_kernel:
        from simple_distributed_machine_learning_tpu.analysis.kernels import (
            KERNEL_FAMILIES,
        )
        from simple_distributed_machine_learning_tpu.analysis.programs import (
            default_registry_reports,
        )
        reports = default_registry_reports()
        gating = [f for r in reports for f in r.findings
                  if f.family in KERNEL_FAMILIES or f.rule == "trace.failed"]
        for f in gating:
            print("\n".join("  " + ln for ln in f.format().splitlines()))
        for r in reports:
            rows = [h for h in r.hbm if h.op.startswith("kernel.")]
            if rows:
                print(f"{r.name}: "
                      + ", ".join(f"{h.program} {h.op}="
                                  f"{h.bytes_per_tick}B" for h in rows))
        # kernel paths gate at ANY severity (zero unproven is the
        # contract), and the whole report must still be ERROR-free so the
        # SDML_LINT_INJECT drill trips this preflight too
        kern_ok = (not gating
                   and all(r.ok(args.fail_on or "error") for r in reports))
        print(f"analysis --serve-kernel: {len(reports)} layouts "
              f"{'kernel-clean' if kern_ok else 'FLAGGED'}")
        ok &= kern_ok

    if args.serve_protocol:
        import dataclasses as _dc
        import os as _os

        from simple_distributed_machine_learning_tpu.analysis.protocol import (
            INVARIANTS,
            CLEAN,
            check_protocol,
        )
        cfg = CLEAN if args.depth is None else _dc.replace(
            CLEAN, depth=args.depth)
        report = check_protocol(cfg)
        # the SDML_LINT_INJECT gate drill, mirrored inline (importing
        # programs.py's helper would pull jax into this jax-free mode)
        tag = _os.environ.get("SDML_LINT_INJECT")
        if tag:
            from simple_distributed_machine_learning_tpu.analysis.report import (  # noqa: E501
                Finding,
                Severity,
            )
            report.findings.append(Finding(
                rule=f"injected.{tag}", severity=Severity.ERROR,
                message="seeded ERROR finding injected via "
                        "SDML_LINT_INJECT — the gate drill proving "
                        "--lint preflights actually fail",
                where="SDML_LINT_INJECT", hint="unset SDML_LINT_INJECT"))
        print(report.format(costs=False))
        print(f"model: {cfg.summary()}")
        print(f"invariants: {', '.join(INVARIANTS)}")
        print(f"verdict: {report.verdict}")
        proto_ok = report.ok(args.fail_on or "error")
        print(f"analysis --serve-protocol: "
              f"{'clean' if proto_ok else 'FLAGGED'}")
        ok &= proto_ok
        protocol_violated |= not proto_ok

    if args.fixtures:
        from simple_distributed_machine_learning_tpu.analysis.fixtures import (
            self_test,
        )
        fx_ok, text = self_test()
        print(text)
        print(f"fixture self-test: {'OK' if fx_ok else 'FAILED'}")
        ok &= fx_ok

    if args.fixture is not None:
        from simple_distributed_machine_learning_tpu.analysis.fixtures import (
            FIXTURES,
        )
        if args.fixture not in FIXTURES:
            p.error(f"unknown fixture {args.fixture!r} (see --list)")
        report = FIXTURES[args.fixture].build()
        print(report.format(costs=args.costs))
        ok &= report.ok(args.fail_on or "warning")

    if args.dryrun is not None:
        from simple_distributed_machine_learning_tpu.analysis.preflight import (
            all_ok,
            dryrun_reports,
        )
        reports = dryrun_reports(args.dryrun)
        for r in reports:
            print(r.format(costs=args.costs))
        fail_on = args.fail_on or "error"
        dry_ok = all_ok(reports, fail_on)
        print(f"analysis --dryrun {args.dryrun}: "
              f"{len(reports)} steps {'clean' if dry_ok else 'FLAGGED'}")
        ok &= dry_ok

    # a violated protocol invariant is the loudest possible failure: its
    # own exit code (2), distinct from ordinary lint findings (1), so CI
    # and scripts can branch on "the protocol itself is broken"
    return 0 if ok else (2 if protocol_violated else 1)


if __name__ == "__main__":
    sys.exit(main())
