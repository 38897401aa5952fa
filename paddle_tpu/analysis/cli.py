"""Analyzer CLI: ``python -m paddle_tpu.analysis`` / ``paddle-tpu-lint``.

Two layers behind one command:

- default: the stdlib-pure jaxlint AST sweep (no jax import — runs as a
  CI gate before the heavyweight runtime even installs);
- ``--ir``: ALSO lower + compile the registered program set and evaluate
  the hlolint contracts (ir.py / contracts.py). Requires jax; exits 2
  with a pointed message when it is unavailable so the AST-only path
  stays dependency-free.

``--select``/``--ignore`` work across both layers: JLxxx ids pick AST
rules, IRxxx ids pick program contracts (selecting only IR ids skips the
AST sweep entirely, and vice versa). ``--update-baseline`` (with
``--ir``) rewrites analysis/ir_baseline.json from this run's program-
shape facts — the deliberate way to move a budget.

Exit codes: 0 clean, 1 unsuppressed findings / contract violations /
unparseable files, 2 usage errors (including --ir without jax).
``--json`` emits the machine-readable report (schema canary in
tests/test_analysis_rules.py; the IR block rides under an ``"ir"`` key).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from .core import all_rules, lint_paths
from .ir import IRHarnessError  # stdlib-pure at import time (jax is lazy)


def default_target():
    """The installed paddle_tpu package root (lint the whole tree when no
    path is given)."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _split_ids(value):
    return [s.strip() for s in value.split(",") if s.strip()]


def _partition_ids(ids):
    """(ast_ids, ir_ids) from a mixed --select/--ignore list; None stays
    None for both."""
    if ids is None:
        return None, None
    ast_ids = [i for i in ids if i.upper().startswith("JL")]
    ir_ids = [i for i in ids if i.upper().startswith("IR")]
    return ast_ids, ir_ids


def _import_jax():
    """Import probe for the --ir layer, separated so tests (and broken
    installs) can fail it cleanly."""
    import jax  # noqa: F401

    return jax


def _reexec_on_fake_mesh_if_needed(argv):
    """The --ir contracts need >= 2 devices (the tp=2 mesh) and a bare
    shell has one. One-shot re-exec with the standard 8-fake-device
    host-platform env (tests/_cpu_mesh.py) fixes it. The decision reads
    the environment, never the device list: asking jax for its devices
    would take the chip, and a lint pass has no business holding it. The
    guard env var makes a still-too-small backend fall through to
    `ir.ensure_host_devices`'s pointed IRHarnessError (exit 2) instead of
    exec-looping."""
    pinned = (os.environ.get("JAX_PLATFORMS") == "cpu"
              and "--xla_force_host_platform_device_count"
              in os.environ.get("XLA_FLAGS", ""))
    if pinned or os.environ.get("_PADDLE_TPU_IR_REEXEC"):
        return
    # only a real CLI process may exec-replace itself: a programmatic
    # cli.main() call from a host app/notebook must fall through to
    # ensure_host_devices' pointed IRHarnessError (exit 2) instead of
    # vaporizing the caller's process state
    argv0 = sys.argv[0] or ""
    if not (os.path.basename(argv0) == "paddle-tpu-lint"
            or argv0.endswith(os.path.join("analysis", "__main__.py"))):
        return
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["_PADDLE_TPU_IR_REEXEC"] = "1"
    args = list(sys.argv[1:] if argv is None else argv)
    os.execve(sys.executable,
              [sys.executable, "-m", "paddle_tpu.analysis"] + args, env)


def build_parser():
    ap = argparse.ArgumentParser(
        prog="paddle-tpu-lint",
        description="static analyzer for the paddle_tpu codebase: "
                    "jaxlint (AST jit-hygiene rules) plus, with --ir, "
                    "hlolint (compiled-program contracts)",
    )
    ap.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: the "
                         "installed paddle_tpu package)")
    ap.add_argument("--ir", action="store_true",
                    help="also lower+compile the registered serving/train "
                         "programs and evaluate the IR contracts "
                         "(requires jax)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="with --ir: rewrite analysis/ir_baseline.json "
                         "from this run's program-shape facts")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the JSON report instead of text")
    ap.add_argument("--select", type=_split_ids, default=None,
                    metavar="IDS", help="only run these rule/contract ids "
                    "(comma-separated, e.g. JL001,IR002)")
    ap.add_argument("--ignore", type=_split_ids, default=None,
                    metavar="IDS", help="skip these rule/contract ids")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="also print suppressed findings (text mode; the "
                         "JSON report always carries them)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule + contract catalog and exit")
    return ap


def _list_rules():
    for rule in all_rules():
        print(f"{rule.id} {rule.name}")
        doc = " ".join((rule.__doc__ or "").split())
        if doc:
            print(f"    {doc}")
        if rule.incident:
            print(f"    incident: {rule.incident}")
    # the contract catalog needs no jax — contracts.py only parses text
    from .contracts import all_contracts

    for contract in all_contracts():
        print(f"{contract.id} {contract.name} (IR contract, --ir)")
        doc = " ".join((contract.__doc__ or "").split())
        if doc:
            print(f"    {doc}")
        if contract.incident:
            print(f"    incident: {contract.incident}")


def _run_ir(args, ir_select, ir_ignore, record_only=False):
    """Lower, compile, and evaluate the IR layer; returns (ir_report
    dict, ok bool). Caller has already verified jax imports.
    `record_only` (a JL-only --select combined with --update-baseline)
    records the baseline from the artifacts but skips contract
    evaluation — the select said to skip this layer's checks."""
    from . import contracts, ir

    t0 = time.perf_counter()
    ir.ensure_host_devices()
    artifacts = ir.default_artifacts()
    if args.update_baseline:
        try:
            path = contracts.save_baseline(artifacts)
        except OSError as e:
            # usage-shaped (--update-baseline into a read-only install);
            # scoped HERE so an OSError escaping the lower+compile pass
            # above (a full disk under a jax compilation cache, say)
            # propagates as the regression it is instead of exiting 2
            raise IRHarnessError(
                f"cannot write baseline {contracts.BASELINE_PATH}: {e}")
        print(f"hlolint: baseline updated: {path}", file=sys.stderr)
    violations = ([] if record_only
                  else contracts.evaluate(artifacts, select=ir_select,
                                          ignore=ir_ignore))
    report = {
        "tool": "hlolint",
        "backend": artifacts[0].backend if artifacts else None,
        "programs": [a.to_json() for a in artifacts],
        "violations": [v.to_json() for v in violations],
        "summary": {
            "programs": len(artifacts),
            "violations": len(violations),
            "duration_s": round(time.perf_counter() - t0, 3),
        },
    }
    return report, not violations


def _print_ir_text(report):
    for prog in report["programs"]:
        colls = {k: v for k, v in prog["collectives"].items() if v}
        cstr = (" ".join(f"{k}={v}" for k, v in sorted(colls.items()))
                or "none")
        facts = prog["facts"]
        print(f"  {prog['name']}: collectives: {cstr}; "
              f"flops={facts.get('flops', 0):.4g} "
              f"bytes={facts.get('bytes_accessed', 0):.4g} "
              f"peak={facts.get('peak_bytes', 0)}")
    for v in report["violations"]:
        print(f"{v['program']}: {v['contract']} {v['name']}: "
              f"{v['message']}")
    s = report["summary"]
    print(f"hlolint: {s['programs']} program(s), "
          f"{s['violations']} violation(s) [{s['duration_s']:.2f}s]")


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.list_rules:
        _list_rules()
        return 0
    if args.update_baseline and not args.ir:
        print("paddle-tpu-lint: --update-baseline requires --ir",
              file=sys.stderr)
        return 2
    if args.ir:
        try:
            _import_jax()
        except Exception as e:
            print("paddle-tpu-lint: --ir needs jax to lower and compile "
                  f"the checked programs, but importing it failed ({e}); "
                  "install the jax_graft toolchain or drop --ir for the "
                  "stdlib-only AST sweep", file=sys.stderr)
            return 2
    if args.select or args.ignore:
        # validate against the actual catalogs, not just the JL/IR prefix:
        # a correctly-prefixed typo (IR01, JL999) would otherwise select
        # zero rules/contracts and exit 0 forever — the same CI false
        # green the prefix check exists to prevent. Both catalogs import
        # without jax (contracts.py only parses text).
        from .contracts import all_contracts

        known = ({r.id for r in all_rules()}
                 | {c.id for c in all_contracts()})
        for flag, ids in (("--select", args.select),
                          ("--ignore", args.ignore)):
            unknown = [i for i in ids or [] if i.upper() not in known]
            if unknown:
                print(f"paddle-tpu-lint: {flag}: unknown rule/contract "
                      f"id(s): {','.join(unknown)} (see --list-rules)",
                      file=sys.stderr)
                return 2
    ast_select, ir_select = _partition_ids(args.select)
    ast_ignore, ir_ignore = _partition_ids(args.ignore)
    if ir_select and not args.ir:
        # a contract-only select without --ir would otherwise run
        # NEITHER layer and exit 0 — a false green in a CI job that
        # dropped the flag
        print("paddle-tpu-lint: --select names IR contract ids "
              f"({','.join(ir_select)}) but --ir was not given; add --ir "
              "to lower and check the programs", file=sys.stderr)
        return 2
    # a --select naming only the other layer's ids means "skip this
    # layer", not "run everything": JL-only select skips IR and back
    run_ast = not (args.select and not ast_select)
    run_ir = args.ir and not (args.select and not ir_select)
    record_only = False
    if args.ir and args.update_baseline and not run_ir:
        run_ir = True       # recording the baseline needs the artifacts,
        record_only = True  # but the JL-only select skips the contracts

    # validate explicit paths even when an IR-only --select skips the AST
    # sweep: a typo'd path exiting 0 because the layer that would have
    # read it was deselected is the same silent false green the id
    # validation above exists to prevent
    for p in args.paths:
        if not os.path.exists(p):
            print(f"paddle-tpu-lint: no such path: {p}", file=sys.stderr)
            return 2

    if run_ir:
        # re-exec only once the IR layer is definitely running — a
        # JL-only select (which skips it) or a usage error above must not
        # pay a full interpreter restart onto the fake mesh — and BEFORE
        # the AST sweep, which the exec'd process would otherwise redo
        # from scratch (the sweep result dies with this process)
        _reexec_on_fake_mesh_if_needed(argv)

    report = None
    if run_ast:
        paths = args.paths or [default_target()]
        # default sweep reports paths as paddle_tpu/... regardless of cwd
        rel_to = os.path.dirname(default_target()) if not args.paths else None
        report = lint_paths(paths, select=ast_select, ignore=ast_ignore,
                            rel_to=rel_to)

    ir_report, ir_ok = None, True
    if run_ir:
        try:
            ir_report, ir_ok = _run_ir(args, ir_select, ir_ignore,
                                       record_only=record_only)
        except IRHarnessError as e:
            # usage-shaped (too few devices, unwritable baseline) — exit
            # 2. A lowering/compile failure of a registered program
            # (jax's XlaRuntimeError is also a RuntimeError) propagates
            # with its traceback: that's a regression, not a usage error.
            print(f"paddle-tpu-lint: --ir: {e}", file=sys.stderr)
            return 2

    ast_ok = report.ok if report is not None else True
    if args.as_json:
        doc = (report.to_json() if report is not None
               else {"version": 1, "tool": "jaxlint", "findings": [],
                     "errors": [], "summary": {"files": 0, "findings": 0,
                                               "suppressed": 0,
                                               "errors": 0,
                                               "duration_s": 0.0}})
        if ir_report is not None:
            doc["ir"] = ir_report
        json.dump(doc, sys.stdout, indent=2)
        print()
        return 0 if (ast_ok and ir_ok) else 1

    if report is not None:
        for f in report.findings:
            if f.suppressed and not args.show_suppressed:
                continue
            print(f.format())
        for path, msg in report.errors:
            print(f"{path}: error: {msg}")
        n = len(report.unsuppressed)
        print(f"jaxlint: {report.files} files, {n} finding(s), "
              f"{len(report.suppressed)} suppressed, "
              f"{len(report.errors)} error(s) "
              f"[{report.duration_s:.2f}s]")
    if ir_report is not None:
        _print_ir_text(ir_report)
    return 0 if (ast_ok and ir_ok) else 1


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
