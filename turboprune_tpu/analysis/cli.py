"""graftlint CLI: ``python -m turboprune_tpu.analysis [paths...]``.

Exit codes (the contract scripts/check.sh and CI build on):
  0 — analyzed clean: zero unwaived findings
  1 — at least one unwaived finding (or a failed --jaxpr-audit diff)
  2 — usage / environment error (bad path, unknown rule in --select,
      git unavailable for --changed, jax unavailable for --jaxpr-audit)

Modes:

* per-file (default) — the lexical rules over the given paths;
* ``--project`` — per-file PLUS the interprocedural layer (symbol
  table + call graph, rules fire through call chains with call-path
  traces) PLUS the config rules over every ``*.yaml`` under the paths.
  This is the pre-PR gate: ``--project turboprune_tpu conf tests``;
* ``--changed [BASE]`` — per-file rules over only the ``.py``/``.yaml``
  files changed vs ``git merge-base HEAD BASE`` (default ``main``), plus
  untracked files, so the fast half of the gate stays fast as the repo
  grows and doesn't drag in files that only changed ON main. Project
  mode intentionally has no --changed variant: call graphs and config
  cross-checks are whole-repo properties;
* ``--jaxpr-audit [ENTRY]`` — trace the real train/eval step (or a
  ``file.py:builder`` entry) under ``--dtype-policy`` and diff the
  jaxpr's convert_element_type ops against the static dtype findings
  and waivers (jaxpr_audit.py). Needs jax importable; everything else
  here runs with no accelerator stack;
* ``--sanitize [TARGET]`` — the runtime mirror of the concurrency rules
  (sanitizer.py): wrap ``threading.Lock``/``RLock``/``Condition``, drive
  the PrefetchEngine / FleetEngine load smokes (or a ``file.py:builder``
  target), fail on observed lock-order cycles and on shared-attribute
  races the static rules did not predict;
* ``--exec-manifest [emit|diff|print]`` — statically enumerate the
  compile surface (jit entries, compile sites, bucket sets, plan kinds)
  into analysis/exec_manifest.json; ``diff`` fails when the surface has
  drifted from the checked-in manifest (exec_manifest.py);
* ``--compile-audit [TARGET]`` — the runtime mirror of the manifest
  (compile_audit.py): patch jax's compile funnel, drive the serving /
  train smokes, and fail on any XLA compile the manifest does not
  explain. Needs jax, like --jaxpr-audit;
* ``--rule-docs`` — print the generated rule-catalog markdown table
  (the source of README.md's marked block).

With no paths it analyzes the installed ``turboprune_tpu`` package — the
same invocation the self-gate test makes, so "the linter passes" means the
same thing locally, in CI, and in tests/test_analysis.py.
"""

from __future__ import annotations

import argparse
import subprocess
import sys
from pathlib import Path
from typing import Optional, Sequence

from .conf_rules import CONF_RULES
from .core import RULES, analyze_files, analyze_paths, analyze_project
from .reporters import render_json, render_sarif, render_text

_EPILOG = """\
exit codes:
  0  analyzed clean: zero unwaived findings (jaxpr audit: clean diff;
     exec-manifest diff: no drift; compile audit: every compile
     attributed)
  1  at least one unwaived finding (jaxpr audit: unexplained upcast or
     unwaived static dtype finding; sanitize: observed lock-order cycle
     or a race with no static finding; exec-manifest diff: compile
     surface drifted vs the checked-in manifest; compile audit: a
     runtime XLA compile no manifest entry explains, or a compiled
     (plan, bucket) outside the declared surface)
  2  usage or environment error (bad path, unknown rule in --select,
     git unavailable for --changed, jax unavailable for
     --jaxpr-audit/--compile-audit, missing manifest)
"""


def _default_paths() -> list:
    return [str(Path(__file__).resolve().parents[1])]


def _default_project_paths() -> list:
    pkg = Path(__file__).resolve().parents[1]
    paths = [str(pkg)]
    conf = pkg.parent / "conf"
    if conf.is_dir():
        paths.append(str(conf))
    return paths


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m turboprune_tpu.analysis",
        description=(
            "graftlint: JAX-aware static analysis (host syncs in jit, "
            "retrace hazards, PRNG key reuse, rank-conditional "
            "collectives, donated-buffer reads, swallowed exceptions, "
            "dtype-flow upcast/promotion hazards; --project adds "
            "interprocedural call-chain analysis and conf/ schema "
            "cross-checking; --jaxpr-audit grounds the dtype rules in "
            "the traced jaxpr)"
        ),
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument(
        "paths",
        nargs="*",
        help="files or directories (default: the turboprune_tpu package)",
    )
    p.add_argument(
        "--project",
        action="store_true",
        help=(
            "whole-project mode: interprocedural jit/RNG/collective/dtype "
            "analysis over the call graph plus conf/*.yaml schema "
            "cross-checks, on top of the per-file rules"
        ),
    )
    p.add_argument(
        "--changed",
        nargs="?",
        const="main",
        metavar="BASE",
        help=(
            "lint only .py/.yaml files changed vs the merge-base of HEAD "
            "and BASE (default: main), plus untracked files"
        ),
    )
    p.add_argument(
        "--jaxpr-audit",
        nargs="?",
        const="train",
        metavar="ENTRY",
        help=(
            "trace ENTRY ('train', 'eval', 'file.py:builder' or "
            "'pkg.module:builder' returning (fn, args)) under "
            "--dtype-policy and diff jaxpr convert_element_type ops "
            "against static dtype findings and waivers (needs jax)"
        ),
    )
    p.add_argument(
        "--sanitize",
        nargs="?",
        const="all",
        metavar="TARGET",
        help=(
            "graftsan runtime concurrency sanitizer: wrap "
            "threading.Lock/RLock/Condition, drive TARGET ('pipeline', "
            "'fleet', 'all', or 'file.py:builder' returning a callable) "
            "under threaded load, fail on observed lock-order cycles and "
            "on shared-attribute races with no static "
            "unsynchronized-shared-mutation finding (a sanitizer-only "
            "race is a static blind spot)"
        ),
    )
    p.add_argument(
        "--exec-manifest",
        nargs="?",
        const="diff",
        choices=("emit", "diff", "print"),
        metavar="MODE",
        help=(
            "executable-set manifest (exec_manifest.py): statically "
            "enumerate every jit entry, compile site, bucket set and "
            "plan-signature kind; 'emit' writes "
            "analysis/exec_manifest.json, 'diff' (default) rebuilds and "
            "fails on drift vs the checked-in file, 'print' dumps the "
            "fresh manifest"
        ),
    )
    p.add_argument(
        "--compile-audit",
        nargs="?",
        const="all",
        metavar="TARGET",
        help=(
            "runtime mirror of the executable manifest "
            "(compile_audit.py): patch jax's compile funnel, drive "
            "TARGET ('serve', 'train', 'all', or 'file.py:builder' "
            "returning a callable), and fail on any XLA compile not "
            "attributed to a manifest entry/compile site, or any "
            "compiled (plan, bucket) outside the declared surface "
            "(needs jax)"
        ),
    )
    p.add_argument(
        "--rule-docs",
        action="store_true",
        help=(
            "print the README rule-catalog markdown table generated from "
            "the rule registries (the marked block in README.md must "
            "match — tests/test_analysis.py gates it)"
        ),
    )
    p.add_argument(
        "--jobs",
        type=int,
        default=0,
        metavar="N",
        help=(
            "process-pool width for --project's per-file half "
            "(0 = one per CPU, 1 = serial; finding order is identical "
            "either way)"
        ),
    )
    p.add_argument(
        "--dtype-policy",
        choices=("fp32", "bf16"),
        default="fp32",
        help=(
            "dtype policy for --jaxpr-audit's default entries: fp32 "
            "(default; must audit clean) or bf16 (casts step inputs to "
            "bfloat16 — the mixed-precision acceptance harness)"
        ),
    )
    p.add_argument(
        "--format",
        choices=("text", "json", "sarif"),
        default=None,
        help="report format (default: text; sarif renders CI annotations)",
    )
    p.add_argument(
        "--json",
        action="store_true",
        help="machine-readable JSON report (alias for --format json)",
    )
    p.add_argument(
        "--show-waived",
        action="store_true",
        help="include waived findings in the text report",
    )
    p.add_argument(
        "--select",
        metavar="RULES",
        help="comma-separated rule ids to run (default: all)",
    )
    p.add_argument(
        "--list-rules",
        action="store_true",
        help="print the rule catalog and exit",
    )
    return p


def _changed_python_files(base: str) -> list:
    """Lintable files changed vs the merge-base of HEAD and ``base``
    (NOT the base tip: diffing against an advanced main would drag in
    every file main changed and miss nothing-but-noise), plus untracked
    files. Py and yaml both count — per-file rules for the former, the
    schema-independent conf checks for the latter."""
    merge = subprocess.run(
        ["git", "merge-base", "HEAD", base],
        capture_output=True,
        text=True,
    )
    diff_base = (
        merge.stdout.strip()
        if merge.returncode == 0 and merge.stdout.strip()
        else base
    )
    files: list = []
    for cmd in (
        ["git", "diff", "--name-only", diff_base, "--"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        proc = subprocess.run(
            cmd, capture_output=True, text=True, check=True
        )
        files.extend(proc.stdout.splitlines())
    out = []
    seen = set()
    for f in files:
        if (
            f.endswith((".py", ".yaml", ".yml"))
            and f not in seen
            and Path(f).exists()
        ):
            seen.add(f)
            out.append(f)
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    all_rules = {**{r.id: r for r in RULES.values()}, **CONF_RULES}
    if args.list_rules:
        width = max(len(r) for r in all_rules)
        for rule in RULES.values():
            print(f"{rule.id:<{width}}  [{rule.severity}] {rule.description}")
        for rule in CONF_RULES.values():
            print(
                f"{rule.id:<{width}}  [{rule.severity}] [project] "
                f"{rule.description}"
            )
        return 0

    modes = [
        name
        for name, on in (
            ("--project", args.project),
            ("--changed", bool(args.changed)),
            ("--jaxpr-audit", bool(args.jaxpr_audit)),
            ("--sanitize", bool(args.sanitize)),
            ("--exec-manifest", bool(args.exec_manifest)),
            ("--compile-audit", bool(args.compile_audit)),
            ("--rule-docs", args.rule_docs),
        )
        if on
    ]
    if len(modes) > 1:
        print(
            f"{' and '.join(modes)} are mutually exclusive",
            file=sys.stderr,
        )
        return 2

    fmt = args.format or ("json" if args.json else "text")

    select = None
    if args.select:
        select = [r.strip() for r in args.select.split(",") if r.strip()]
        unknown = [r for r in select if r not in all_rules]
        if unknown:
            print(
                f"unknown rule(s): {', '.join(unknown)} "
                f"(known: {', '.join(sorted(all_rules))})",
                file=sys.stderr,
            )
            return 2

    if args.jaxpr_audit:
        from .jaxpr_audit import AuditError, run_audit

        try:
            return run_audit(
                entry=args.jaxpr_audit, policy=args.dtype_policy
            )
        except AuditError as e:
            print(f"graftlint --jaxpr-audit: {e}", file=sys.stderr)
            return 2

    if args.sanitize:
        from .sanitizer import SanitizeError, run_sanitize

        try:
            return run_sanitize(args.sanitize)
        except SanitizeError as e:
            print(f"graftlint --sanitize: {e}", file=sys.stderr)
            return 2

    if args.rule_docs:
        from .reporters import render_rule_docs

        print(render_rule_docs(), end="")
        return 0

    if args.exec_manifest:
        from .exec_manifest import run_exec_manifest

        try:
            return run_exec_manifest(args.exec_manifest, paths=args.paths)
        except ValueError as e:
            print(f"graftlint --exec-manifest: {e}", file=sys.stderr)
            return 2

    if args.compile_audit:
        from .compile_audit import AuditError, run_compile_audit

        try:
            return run_compile_audit(args.compile_audit)
        except AuditError as e:
            print(f"graftlint --compile-audit: {e}", file=sys.stderr)
            return 2

    try:
        if args.changed:
            if args.paths:
                print(
                    "--changed takes no paths (it derives them from git)",
                    file=sys.stderr,
                )
                return 2
            try:
                files = _changed_python_files(args.changed)
            except (subprocess.CalledProcessError, OSError) as e:
                detail = getattr(e, "stderr", "") or str(e)
                print(
                    f"graftlint --changed: git failed: {detail.strip()}",
                    file=sys.stderr,
                )
                return 2
            if not files:
                print(
                    f"graftlint: no lintable files changed vs {args.changed}"
                )
                return 0
            result = analyze_files(files, select=select)
        elif args.project:
            result = analyze_project(
                args.paths or _default_project_paths(),
                select=select,
                jobs=args.jobs or None,
            )
        else:
            result = analyze_paths(
                args.paths or _default_paths(), select=select
            )
    except (FileNotFoundError, OSError) as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 2

    if fmt == "json":
        print(render_json(result))
    elif fmt == "sarif":
        print(render_sarif(result))
    else:
        print(render_text(result, show_waived=args.show_waived))
    return 1 if result.unwaived else 0
