"""Executable-set manifests: statically bound the compile surface.

Serving and compact-training live or die by ONE property: the set of XLA
executables a process can ever build is finite and known before it boots
(warmup compiles all of them; steady state never compiles). The shape-flow
rules (shape_rules.py) police the hazards that would break that property;
this module writes the property itself down. It statically enumerates

* **entries** — every lexically-traced function body (regions.py), the
  bodies XLA programs are made from;
* **compile_sites** — every ``jax.jit(...)``-wrapper call, with the
  target function's name resolved through one level of factory
  indirection (``jax.jit(make_eval_step(...))`` resolves to the nested
  ``eval_step`` the factory returns), because the runtime module name of
  a compile is ``jit_<fn.__name__>`` and attribution needs that name;
* **bucket_sets** — every declared batch-bucket set: literal int tuples
  assigned to bucket-named symbols in the package and ``batch_buckets``
  (or any bucket-named list) in ``conf/**/*.yaml``;
* **plan_kinds** — every ``PLAN_SIGNATURE_KIND = "..."`` declaration
  (sparse/compact.py, sparse/nm_execute.py, serve/engine.py): the plan
  vocabulary AOT cache keys may carry.

The product (entries+sites) x (bucket union) x (plan kinds) is the entire
legal compile surface. Its *set* is checked in as ``exec_manifest.json``
next to this file (``locked``): entries by (file, name, reason), compile
sites by (file, target), bucket sets, plan kinds by file. Where in its file a
body or a call stands (``line``, ``end``) is a fact about the tree a build
ran on, not part of the lock: ``build_manifest()`` gives it, the checked-in
file does not hold it, and a line that moves is no drift.
``graftlint --exec-manifest diff`` fails when code grows, drops or renames a
jit entry / compile site / bucket / plan kind the lockfile doesn't know
(re-emit to accept), and ``--compile-audit`` (compile_audit.py) holds a real
run to it, with its line windows from a fresh build.

Pure stdlib at import time, like the rest of the package; the yaml parse
degrades to a regex scan when PyYAML is unavailable.
"""

from __future__ import annotations

import ast
import json
import re
from collections import Counter
from pathlib import Path
from typing import Optional

from .core import _collect_project_files, is_test_file
from .project import ProjectIndex
from .regions import build_jit_regions, dotted_name, is_jit_wrapper, unwrap_partial

__all__ = [
    "MANIFEST_PATH",
    "build_manifest",
    "covers",
    "executable_names",
    "load_manifest",
    "locked",
    "run_exec_manifest",
]

MANIFEST_PATH = Path(__file__).resolve().parent / "exec_manifest.json"
MANIFEST_VERSION = 2  # 2: the checked-in file holds no line numbers


def _repo_root() -> Path:
    return Path(__file__).resolve().parents[2]


def _rel(path) -> str:
    p = Path(path).resolve()
    try:
        return p.relative_to(_repo_root()).as_posix()
    except ValueError:
        return p.as_posix()


def _default_paths() -> list:
    pkg = Path(__file__).resolve().parents[1]
    paths = [pkg]
    conf = pkg.parent / "conf"
    if conf.is_dir():
        paths.append(conf)
    return paths


# ----------------------------------------------------------- python scans


def _int_seq(node: ast.AST) -> Optional[list]:
    """A literal tuple/list of >= 1 ints -> the ints; else None."""
    if not isinstance(node, (ast.Tuple, ast.List)) or not node.elts:
        return None
    out = []
    for e in node.elts:
        if not (
            isinstance(e, ast.Constant)
            and isinstance(e.value, int)
            and not isinstance(e.value, bool)
        ):
            return None
        out.append(e.value)
    return out


def _bucket_named(name: Optional[str]) -> bool:
    return bool(name) and "bucket" in name.lower()


def _py_bucket_sets(mi) -> dict:
    """``{"<file>:<symbol>": [ints]}`` for bucket declarations in one
    module: literal int-sequence assigns to bucket-named targets (the
    sequence may sit behind a default_factory lambda, as in the serve
    config schema)."""
    out: dict = {}
    rel = _rel(mi.path)
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        names = [
            t.id
            for t in targets
            if isinstance(t, ast.Name) and _bucket_named(t.id)
        ]
        if not names:
            continue
        seq = _int_seq(value)
        if seq is None:
            for sub in ast.walk(value):
                seq = _int_seq(sub)
                if seq is not None:
                    break
        if seq is not None:
            for name in names:
                out[f"{rel}:{name}"] = seq
    return out


def _site_target(arg: ast.AST, mi, index, graph, scope) -> str:
    """The best static name for what a jit-wrapper call compiles — chosen
    to line up with the runtime module name ``jit_<fn.__name__>``."""
    node = unwrap_partial(arg)
    if isinstance(node, ast.Lambda):
        return "<lambda>"
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr  # bound method: __name__ is the attr tail
    if isinstance(node, ast.Call):
        # factory call: jit(make_eval_step(...)) compiles the nested def
        # the factory returns, and THAT def's name is the runtime name
        callee = index.resolve_call(mi, node.func, scope)
        if callee is not None:
            nested = graph.returns_nested(callee)
            if nested is not None:
                return nested.name
        return dotted_name(node.func) or "?"
    return "?"


def _scan_python(py_files) -> tuple:
    """(entries, compile_sites, bucket_sets, plan_kinds) over the package.

    Test files are excluded: the manifest bounds what SHIPPING code can
    compile; tests construct throwaway jits on purpose. The analysis
    package itself is excluded too — its audit drivers jit on purpose,
    and the runtime half (compile_audit._repo_site) symmetrically skips
    analysis/ frames when attributing."""
    from .rules import _own_statements, _walk_no_nested_defs

    analysis_dir = Path(__file__).resolve().parent
    contexts = []
    for f in py_files:
        if is_test_file(f):
            continue
        if Path(f).resolve().parent == analysis_dir:
            continue
        try:
            tree = ast.parse(Path(f).read_text(encoding="utf-8"))
        except SyntaxError:
            continue  # the lint gate owns parse errors
        contexts.append((str(f), tree))

    class _Ctx:  # the minimal shape ProjectIndex.build consumes
        def __init__(self, path, tree):
            self.path, self.tree = path, tree

    index = ProjectIndex.build(_Ctx(p, t) for p, t in contexts)
    from .callgraph import CallGraph

    graph = CallGraph(index)

    entries: list = []
    sites: list = []
    bucket_sets: dict = {}
    plan_kinds: dict = {}

    for path, tree in contexts:
        rel = _rel(path)
        for r in build_jit_regions(tree):
            entries.append(
                {
                    "name": getattr(r.node, "name", "<lambda>"),
                    "file": rel,
                    "line": r.start,
                    "end": r.end,
                    "reason": r.reason,
                }
            )
        mi = index.module_for_path(path)
        if mi is None:
            continue
        bucket_sets.update(_py_bucket_sets(mi))
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "PLAN_SIGNATURE_KIND"
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                plan_kinds[node.value.value] = f"{rel}:{node.lineno}"
        scopes = [(None, mi.tree.body)]
        scopes.extend(
            (fi, fi.node.body)
            for fi in index.functions.values()
            if fi.path == mi.path
        )
        for scope, body in scopes:
            for node in _walk_no_nested_defs(_own_statements(body)):
                if (
                    isinstance(node, ast.Call)
                    and is_jit_wrapper(node.func)
                    and node.args
                ):
                    sites.append(
                        {
                            "target": _site_target(
                                node.args[0], mi, index, graph, scope
                            ),
                            "file": rel,
                            "line": node.lineno,
                        }
                    )
    return entries, sites, bucket_sets, plan_kinds


# ------------------------------------------------------------- yaml scans

_YAML_BUCKET_RE = re.compile(
    r"^(\w*bucket\w*)\s*:\s*\[([0-9,\s]+)\]", re.MULTILINE
)


def _walk_yaml(data, prefix, out) -> None:
    if isinstance(data, dict):
        for k, v in data.items():
            key = str(k)
            if (
                _bucket_named(key)
                and isinstance(v, list)
                and v
                and all(isinstance(i, int) and not isinstance(i, bool) for i in v)
            ):
                out[f"{prefix}:{key}"] = list(v)
            else:
                _walk_yaml(v, prefix, out)
    elif isinstance(data, list):
        for v in data:
            _walk_yaml(v, prefix, out)


def _yaml_bucket_sets(yaml_files) -> dict:
    out: dict = {}
    for f, _root in yaml_files:
        rel = _rel(f)
        try:
            text = Path(f).read_text(encoding="utf-8")
        except OSError:
            continue
        try:
            import yaml

            _walk_yaml(yaml.safe_load(text), rel, out)
        except Exception:  # graftlint: disable=broad-except -- no PyYAML / unparsable yaml degrades to the regex scan; conf lint owns yaml errors
            for m in _YAML_BUCKET_RE.finditer(text):
                vals = [int(x) for x in m.group(2).split(",") if x.strip()]
                if vals:
                    out[f"{rel}:{m.group(1)}"] = vals
    return out


# ------------------------------------------------------------ the manifest


def build_manifest(paths=None) -> dict:
    """The static compile-surface manifest over ``paths`` (default: the
    package + conf/). Deterministic: everything sorted, paths repo-relative
    posix — same tree, same JSON, so ``diff`` is a pure content check."""
    py_files, yaml_files = _collect_project_files(paths or _default_paths())
    entries, sites, bucket_sets, plan_kinds = _scan_python(py_files)
    bucket_sets.update(_yaml_bucket_sets(yaml_files))
    entries.sort(key=lambda e: (e["file"], e["line"], e["name"]))
    sites.sort(key=lambda s: (s["file"], s["line"], s["target"]))
    buckets = sorted({b for vals in bucket_sets.values() for b in vals})
    return {
        "version": MANIFEST_VERSION,
        "entries": entries,
        "compile_sites": sites,
        "bucket_sets": {k: bucket_sets[k] for k in sorted(bucket_sets)},
        "buckets": buckets,
        "plan_kinds": {k: plan_kinds[k] for k in sorted(plan_kinds)},
    }


def load_manifest(path=None) -> Optional[dict]:
    p = Path(path) if path else MANIFEST_PATH
    if not p.is_file():
        return None
    return json.loads(p.read_text(encoding="utf-8"))


def executable_names(manifest: dict) -> set:
    """Every function name the manifest says may become an XLA module:
    runtime compiles are named ``jit_<fn.__name__>``, so attribution is a
    membership test against this set."""
    return {e["name"] for e in manifest.get("entries", ())} | {
        s["target"] for s in manifest.get("compile_sites", ())
    }


def covers(manifest: dict, plan_kind: str, bucket: int) -> bool:
    """Is (plan kind, bucket) inside the statically-declared surface?"""
    return plan_kind in manifest.get("plan_kinds", {}) and int(bucket) in set(
        manifest.get("buckets", ())
    )


def locked(manifest: dict) -> dict:
    """What the lockfile locks of a manifest: the same keys with every line
    number dropped (an entry's ``line`` / ``end``, a site's ``line``, the
    ``:line`` after a plan kind's file). Two bodies of one name and reason in
    one file (two lambdas) stay two."""
    return {
        **manifest,
        "version": MANIFEST_VERSION,
        "entries": sorted(
            ({k: e[k] for k in ("file", "name", "reason")} for e in manifest.get("entries", ())),
            key=lambda e: (e["file"], e["name"], e["reason"]),
        ),
        "compile_sites": sorted(
            ({k: s[k] for k in ("file", "target")} for s in manifest.get("compile_sites", ())),
            key=lambda s: (s["file"], s["target"]),
        ),
        "plan_kinds": {k: v.partition(":")[0] for k, v in manifest.get("plan_kinds", {}).items()},
    }


def _dumps(manifest: dict) -> str:
    return json.dumps(manifest, indent=1, sort_keys=True) + "\n"


def _diff_lists(name, old, new, print_fn) -> int:
    o = Counter(json.dumps(x, sort_keys=True) for x in old)
    n = Counter(json.dumps(x, sort_keys=True) for x in new)
    bad = 0
    for item in sorted((n - o).elements()):
        print_fn(f"  + {name}: {item}")
        bad += 1
    for item in sorted((o - n).elements()):
        print_fn(f"  - {name}: {item}")
        bad += 1
    return bad


def run_exec_manifest(mode: str = "diff", paths=None, print_fn=print) -> int:
    """CLI driver: ``emit`` writes the manifest's lock (``locked``),
    ``print`` dumps the manifest with the tree's line numbers, ``diff`` (the
    check.sh stage) rebuilds and compares lock to lock with the checked-in
    file — exit 1 on drift, with the drift itemized."""
    if mode not in ("emit", "diff", "print"):
        raise ValueError(
            f"unknown --exec-manifest mode {mode!r}; expected emit, diff "
            "or print"
        )
    manifest = build_manifest(paths)
    if mode == "print":
        print_fn(_dumps(manifest).rstrip("\n"))
        return 0
    if mode == "emit":
        MANIFEST_PATH.write_text(_dumps(locked(manifest)), encoding="utf-8")
        print_fn(
            f"exec-manifest: wrote {_rel(MANIFEST_PATH)} "
            f"({len(manifest['entries'])} entries, "
            f"{len(manifest['compile_sites'])} compile sites, "
            f"{len(manifest['buckets'])} buckets, "
            f"{len(manifest['plan_kinds'])} plan kinds)"
        )
        return 0
    checked_in = load_manifest()
    if checked_in is None:
        print_fn(
            f"exec-manifest: {_rel(MANIFEST_PATH)} missing — run "
            "--exec-manifest emit and commit it"
        )
        return 1
    # A file from before version 2 holds lines: they are dropped, not compared.
    checked_in, manifest = locked(checked_in), locked(manifest)
    bad = 0
    for key in ("entries", "compile_sites"):
        bad += _diff_lists(key, checked_in.get(key, []), manifest[key], print_fn)
    for key in ("bucket_sets", "plan_kinds"):
        old, new = checked_in.get(key, {}), manifest[key]
        for k in sorted(set(old) | set(new)):
            if old.get(k) != new.get(k):
                print_fn(f"  ~ {key}[{k}]: {old.get(k)} -> {new.get(k)}")
                bad += 1
    if checked_in.get("buckets") != manifest["buckets"]:
        print_fn(
            f"  ~ buckets: {checked_in.get('buckets')} -> "
            f"{manifest['buckets']}"
        )
        bad += 1
    if bad:
        print_fn(
            f"exec-manifest: {bad} difference(s) vs {_rel(MANIFEST_PATH)} — "
            "the compile surface changed; review and re-emit"
        )
        return 1
    print_fn(
        f"exec-manifest: clean ({len(manifest['entries'])} entries, "
        f"{len(manifest['compile_sites'])} compile sites, "
        f"buckets {manifest['buckets']}, "
        f"plan kinds {sorted(manifest['plan_kinds'])})"
    )
    return 0
