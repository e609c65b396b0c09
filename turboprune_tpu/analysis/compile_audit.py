"""``--compile-audit``: every runtime XLA compile attributed to the
static executable manifest — the runtime mirror of exec_manifest.py,
exactly as jaxpr_audit.py mirrors the dtype rules and sanitizer.py the
thread rules.

The manifest claims the compile surface is finite and statically known.
This mode checks the claim against what XLA actually does: it patches the
one funnel every compile goes through (``jax._src.compiler
.backend_compile_and_load``), drives the package's real compile-heavy
subsystems (the serving engine's bucket warmup; the synthetic train step),
and demands that every compile observed in the measured window is attributed
to a manifest entry or compile site:

* by NAME — a compiled module is named ``jit_<fn.__name__>`` (non-word
  characters mangled to ``_``), so ``jit__apply`` attributes to the
  ``jax.jit(self._apply)`` compile site and ``jit_train_step`` to the
  mesh factories' ``train_step`` target;
* by SITE — failing that, the innermost package stack frame under the
  compile must sit inside a manifest entry's span or on a compile-site
  line.

A compile neither explains is an executable the static layer never
enumerated — the exact hazard the shape rules exist to prevent (bucket
escapes, data-dependent shapes) — and fails the run. The serving driver
additionally checks that every bucket it compiled and its plan kind are
``covers()``-ed by the manifest, tying the runtime AOT cache key
vocabulary to the static declaration.

Driver discipline: all setup (model init, mask folding, array literals)
happens OUTSIDE the ledger window — eager jnp ops compile tiny modules
(``jit_iota``, ...) that are infrastructure, not part of the serving
surface. The measured window contains only the steady-state paths whose
compile behavior the manifest bounds.

jax imports live inside functions; the package stays importable with no
accelerator stack. Exit codes follow the CLI contract: 0 clean, 1
unattributed compile / uncovered bucket, 2 usage or environment error.
"""

from __future__ import annotations

import re
import threading
import traceback
from pathlib import Path
from typing import Callable, Optional

from .drivers import default_step_entry, resolve_runtime_target
from .exec_manifest import build_manifest, covers, executable_names, load_manifest

__all__ = ["AuditError", "CompileLedger", "run_compile_audit"]

_PKG_ROOT = Path(__file__).resolve().parents[1]
_ANALYSIS_DIR = Path(__file__).resolve().parent


class AuditError(RuntimeError):
    """Usage/environment error (CLI maps it to exit code 2)."""


def _runtime_name(fn_name: str) -> str:
    """The MLIR module name jax gives a compiled ``fn_name`` — e.g.
    ``<lambda>`` becomes ``jit__lambda_``."""
    return "jit_" + re.sub(r"\W", "_", fn_name)


def _module_name(module) -> str:
    try:
        attr = module.operation.attributes["sym_name"]
        value = getattr(attr, "value", None)
        return str(value) if value is not None else str(attr).strip('"')
    except Exception:  # graftlint: disable=broad-except -- MLIR binding drift degrades to "?", which the report shows as unattributed
        return "?"


def _repo_site() -> Optional[tuple]:
    """Innermost package frame (outside analysis/) on the current stack:
    the repo line that triggered this compile."""
    for frame in reversed(traceback.extract_stack()):
        p = Path(frame.filename)
        try:
            p.relative_to(_ANALYSIS_DIR)
            continue  # the audit's own frames don't attribute anything
        except ValueError:
            pass
        try:
            p.relative_to(_PKG_ROOT)
        except ValueError:
            continue
        return str(p), frame.lineno
    return None


class CompileLedger:
    """Context manager: patch ``backend_compile_and_load``, record every
    compile in the window as ``{"name", "site"}`` (site = innermost repo frame).
    Thread-safe — the serving engine compiles under its own lock, and
    nothing stops a driver from compiling from several threads."""

    def __init__(self):
        self.records: list = []
        self._mu = threading.Lock()
        self._orig = None

    def __enter__(self) -> "CompileLedger":
        import jax._src.compiler as host

        # The one funnel every jit/AOT compile of a loaded executable goes
        # through on the installed jax. If it moves, this raises
        # AttributeError and the ledger test fails — an audit that patches
        # nothing would report zero compiles, not an error.
        orig = host.backend_compile_and_load
        ledger = self

        def patched(*args, **kwargs):
            module = next(
                (
                    a
                    for a in list(args) + list(kwargs.values())
                    if hasattr(a, "operation")
                ),
                None,
            )
            rec = {
                "name": _module_name(module) if module is not None else "?",
                "site": _repo_site(),
            }
            with ledger._mu:
                ledger.records.append(rec)
            return orig(*args, **kwargs)

        host.backend_compile_and_load = patched
        self._orig = orig
        return self

    def __exit__(self, *exc) -> None:
        if self._orig is not None:
            import jax._src.compiler as host

            host.backend_compile_and_load = self._orig
            self._orig = None


def _attribution(rec: dict, names: set, spans: list) -> Optional[str]:
    """How the manifest explains one compile record, or None."""
    for n in names:
        if rec["name"] == _runtime_name(n):
            return f"name match: {n}"
    site = rec["site"]
    if site is not None:
        file, line = site
        rel = _posix_rel(file)
        for sfile, start, end, label in spans:
            if rel == sfile and start <= line <= end:
                return f"site match: {label} at {sfile}:{start}"
    return None


def _posix_rel(path: str) -> str:
    p = Path(path).resolve()
    try:
        return p.relative_to(_PKG_ROOT.parent).as_posix()
    except ValueError:
        return p.as_posix()


def _manifest_spans(manifest: dict, tree: dict) -> list:
    """(file, start, end, label) windows a triggering repo frame may sit
    in: the entries and compile sites the checked-in ``manifest`` knows,
    where ``tree`` (a fresh ``build_manifest()``) finds them; the lockfile
    holds no line numbers. Compile-site lines get a small slop: the jit call
    and the ``.lower()``/``.compile()`` it feeds span a few lines."""
    entries = {(e["file"], e["name"], e["reason"]) for e in manifest.get("entries", ())}
    sites = {(s["file"], s["target"]) for s in manifest.get("compile_sites", ())}
    spans = []
    for e in tree.get("entries", ()):
        if (e["file"], e["name"], e["reason"]) in entries:
            spans.append((e["file"], e["line"], e["end"], f"entry {e['name']}"))
    for s in tree.get("compile_sites", ()):
        if (s["file"], s["target"]) in sites:
            spans.append((s["file"], s["line"], s["line"] + 20, f"site {s['target']}"))
    return spans


# ------------------------------------------------------------------ drivers


def _drive_serve(ledger: CompileLedger, manifest: dict) -> list:
    """A real InferenceEngine over a fresh (all-ones-masked) checkpoint:
    warmup compiles every bucket, predict must then compile nothing.
    Returns coverage problems (unattributed compiles are the caller's
    diff)."""
    import jax
    import numpy as np

    from ..models import create_model
    from ..ops.masking import make_masks
    from ..serve.engine import InferenceEngine
    from ..train.state import init_variables

    model = create_model("resnet18", num_classes=10, dataset_name="CIFAR10")
    variables = init_variables(
        # graftlint: disable=rng-key-reuse -- fixed key: the audit is a reproducible gate, not a sampler
        model, jax.random.PRNGKey(0), (1, 8, 8, 3)
    )
    params = variables["params"]
    masks = make_masks(params)
    engine = InferenceEngine(
        model,
        params,
        masks,
        variables.get("batch_stats", {}),
        input_shape=(8, 8, 3),
        buckets=(1, 8),  # members of the declared conf bucket sets
    )
    x = np.zeros((3, 8, 8, 3), np.float32)

    before = len(ledger.records)
    with ledger:
        engine.warmup()
        engine.predict(x)  # rides the warmed bucket: zero new compiles
    compiles = len(ledger.records) - before

    problems = []
    if compiles != len(engine.buckets):
        problems.append(
            f"serve: expected exactly {len(engine.buckets)} compiles "
            f"(one per bucket), observed {compiles} — steady-state "
            "predict recompiled"
        )
    kind = engine._plan_signature[0]
    for b in engine.compiled_buckets:
        if not covers(manifest, kind, b):
            problems.append(
                f"serve: compiled (plan={kind!r}, bucket={b}) is outside "
                "the manifest's declared plan kinds x buckets"
            )
    return problems


def _drive_train(ledger: CompileLedger, manifest: dict) -> list:
    """The synthetic train step (shared with --jaxpr-audit) jitted and
    executed once: exactly one compile, named for the step."""
    import jax

    fn, args = default_step_entry("train")
    jitted = jax.jit(fn)
    before = len(ledger.records)
    with ledger:
        out = jitted(*args)
        jax.block_until_ready(out)
    compiles = len(ledger.records) - before
    if compiles != 1:
        return [
            f"train: expected exactly 1 compile for the jitted step, "
            f"observed {compiles}"
        ]
    return []


def _custom_drive(spec: str) -> Callable:
    def drive(ledger: CompileLedger, _manifest: dict) -> list:
        from .drivers import load_builder

        builder, _paths = load_builder(
            spec, error_cls=AuditError, what="--compile-audit target"
        )
        fn = builder()  # setup outside the window, like the built-ins
        if not callable(fn):
            raise AuditError(
                f"--compile-audit: {spec} must return a callable to drive"
            )
        with ledger:
            fn()
        return []

    return drive


# ------------------------------------------------------------------- runner


def run_compile_audit(target: str = "all", print_fn: Callable = print) -> int:
    """Drive, record, attribute. Returns 0 (every compile attributed and
    every (plan, bucket) covered) or 1; raises AuditError for usage
    problems."""
    try:
        import jax  # noqa: F401
    except ImportError as e:  # pragma: no cover - environment-dependent
        raise AuditError(f"--compile-audit needs jax importable: {e}") from e

    target = target or "all"
    if target == "all":
        drivers = [("serve", _drive_serve), ("train", _drive_train)]
    else:
        kind, payload = resolve_runtime_target(
            target,
            {"serve": _drive_serve, "train": _drive_train},
            error_cls=AuditError,
            what="--compile-audit target",
        )
        drivers = [
            (target, payload if kind == "named" else _custom_drive(target))
        ]

    manifest = load_manifest()
    if manifest is None:
        raise AuditError(
            "exec_manifest.json missing — run --exec-manifest emit and "
            "commit it before auditing against it"
        )
    names = executable_names(manifest)
    spans = _manifest_spans(manifest, build_manifest())

    ledger = CompileLedger()
    problems: list = []
    for name, drive in drivers:
        n0 = len(ledger.records)
        problems.extend(drive(ledger, manifest))
        print_fn(
            f"compile-audit: drove {name} "
            f"({len(ledger.records) - n0} compile(s) in the window)"
        )

    unattributed = []
    for rec in ledger.records:
        why = _attribution(rec, names, spans)
        site = rec["site"]
        where = f"{_posix_rel(site[0])}:{site[1]}" if site else "<no repo frame>"
        if why is None:
            unattributed.append(rec)
            print_fn(f"  {rec['name']} from {where} [UNATTRIBUTED]")
        else:
            print_fn(f"  {rec['name']} from {where} [{why}]")

    for p in problems:
        print_fn(f"compile-audit: {p}")
    ok = not unattributed and not problems
    print_fn(
        f"compile-audit: {len(ledger.records)} compile(s), "
        f"{len(unattributed)} unattributed, {len(problems)} coverage "
        f"problem(s) — {'clean' if ok else 'NOT clean'}"
    )
    return 0 if ok else 1
