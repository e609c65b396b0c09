"""``--jaxpr-audit``: ground the static dtype rules in the real jaxpr.

The dtype-flow rules (dtype_rules.py) are an at-rest approximation of
JAX's promotion table; the compiler's own record of every promotion is the
``convert_element_type`` equations in the jaxpr. This mode traces the real
train/eval step under a declared dtype policy and diffs the two views:

* every reduced->f32/f64 ``convert_element_type`` in the traced jaxpr is
  located via its source frame and matched against (a) dtype-rule waivers,
  (b) static dtype findings, (c) an explicit cast on the source line
  (``astype``/``convert_element_type``/``asarray`` — a visible decision);
* an upcast none of those explain is a static-analysis blind spot and
  fails the audit, as does any unwaived static dtype finding over the
  audited files (static and dynamic must BOTH be clean).

Under the default fp32 policy nothing is reduced, so the synthetic-task
step must audit to zero upcasts — that's the regression gate. Under
``--dtype-policy bf16`` the audit is the acceptance harness for ROADMAP
item 6's mixed-precision PR: it shows exactly which promotions the bf16
step would reintroduce, before any of it lands.

jax imports live inside functions: the analysis package stays importable
with no accelerator stack, and only this mode pays for the tracer.

Entry points: ``train`` / ``eval`` build the synthetic-task step (tiny
resnet18, CIFAR-shaped inputs); ``path/to/file.py:fn`` or
``pkg.module:fn`` calls ``fn()`` which must return ``(step_fn, args)`` —
the audit traces ``step_fn(*args)`` and statically analyzes the file that
defines it.
"""

from __future__ import annotations

from pathlib import Path
from typing import Callable, Iterator, Optional

from .core import analyze_paths
from .drivers import default_step_entry, resolve_runtime_target

__all__ = ["AuditError", "DTYPE_RULE_IDS", "run_audit"]

DTYPE_RULE_IDS = (
    "silent-upcast",
    "weak-type-promotion",
    "scan-carry-dtype-drift",
    "missing-preferred-element-type",
)

_REDUCED_NAMES = {"bfloat16", "float16"}
_WIDE_NAMES = {"float32", "float64"}
_EXPLICIT_MARKERS = ("astype", "convert_element_type", "asarray")
_NEAR_LINES = 2  # inference anchors vs trace frames can differ on multiline exprs


class AuditError(RuntimeError):
    """Usage/environment error (CLI maps it to exit code 2)."""


# ------------------------------------------------------------- entries


def _load_entry(entry: str, policy: str):
    """``(step_fn, args, static_paths)`` for an entry spec. Named entries
    and builder specs resolve through the shared registry (drivers.py), so
    the three runtime modes accept identical target grammar."""
    pkg = Path(__file__).resolve().parents[1]
    kind, payload = resolve_runtime_target(
        entry,
        {"train": "train", "eval": "eval"},
        error_cls=AuditError,
        what="--jaxpr-audit entry",
    )
    if kind == "named":
        fn, args = default_step_entry(payload, policy)
        return fn, args, [pkg / "train", pkg / "ops"]
    builder, static_paths = payload
    fn, args = builder()
    return fn, args, static_paths


# --------------------------------------------------------- jaxpr walking


def _sub_jaxprs(v) -> Iterator:
    items = v if isinstance(v, (tuple, list)) else (v,)
    for x in items:
        inner = getattr(x, "jaxpr", None)  # ClosedJaxpr -> Jaxpr
        if inner is not None and hasattr(inner, "eqns"):
            yield inner
        elif hasattr(x, "eqns"):
            yield x


def _iter_eqns(jaxpr) -> Iterator:
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _source_site(eqn) -> Optional[tuple]:
    """(file, line) of the first user frame behind an equation, or None
    when the traceback holds none. source_info_util is jax-internal: a
    changed signature raises here and fails TestJaxprAudit, it does not
    degrade to "no source frame" (which reports every upcast unexplained)."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None
    return str(frame.file_name), int(frame.start_line)


def _dtype_name(d) -> str:
    try:
        import numpy as np

        return str(np.dtype(d))
    # graftlint: disable=broad-except -- extended dtypes (key<fry>) reject np.dtype(); the raw repr is fine for the report
    except Exception:
        return str(d)


def _collect_upcasts(closed_jaxpr) -> tuple:
    """``(total_eqns, [(file|None, line|None, old, new), ...])``."""
    total = 0
    upcasts = []
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        total += 1
        if eqn.primitive.name != "convert_element_type":
            continue
        new = _dtype_name(eqn.params.get("new_dtype"))
        old = _dtype_name(getattr(eqn.invars[0].aval, "dtype", ""))
        if old in _REDUCED_NAMES and new in _WIDE_NAMES:
            site = _source_site(eqn)
            file, line = site if site else (None, None)
            upcasts.append((file, line, old, new))
    return total, upcasts


# --------------------------------------------------------------- the diff


def _same_file(a: Optional[str], b: str) -> bool:
    if a is None:
        return False
    try:
        return Path(a).resolve() == Path(b).resolve()
    except OSError:
        return False


def _explain(file, line, old, new, result) -> tuple:
    """``(status, detail)``: how the static layer accounts for one upcast.
    status: 'waiver' | 'finding' | 'explicit-cast' | 'unexplained'."""
    if file is None:
        return "unexplained", "no source frame"
    for w in result.waivers:
        if (
            w.rules & set(DTYPE_RULE_IDS)
            and _same_file(file, w.file)
            and abs(w.applies_to - line) <= _NEAR_LINES
        ):
            return "waiver", w.reason or "no reason given"
    for f in result.findings:
        if (
            f.rule in DTYPE_RULE_IDS
            and _same_file(file, f.file)
            and abs(f.line - line) <= _NEAR_LINES
        ):
            if f.waived:
                return "waiver", f.waiver_reason or "no reason given"
            return "finding", f"{f.rule} at {f.file}:{f.line}"
    try:
        text = Path(file).read_text(encoding="utf-8").splitlines()[line - 1]
    except (OSError, IndexError):
        text = ""
    if any(m in text for m in _EXPLICIT_MARKERS):
        return "explicit-cast", text.strip()
    return "unexplained", text.strip() or "??"


def run_audit(
    entry: str = "train",
    policy: str = "fp32",
    print_fn: Callable = print,
) -> int:
    """Trace, collect reduced->wide converts, diff against the static
    layer. Returns 0 (clean) or 1 (unexplained upcasts and/or unwaived
    static dtype findings). Raises AuditError for usage problems."""
    try:
        import jax
    except ImportError as e:  # pragma: no cover - environment-dependent
        raise AuditError(f"--jaxpr-audit needs jax importable: {e}") from e

    fn, args, static_paths = _load_entry(entry, policy)
    closed = jax.make_jaxpr(fn)(*args)
    total, upcasts = _collect_upcasts(closed)

    result = analyze_paths(static_paths, select=list(DTYPE_RULE_IDS))
    unwaived_static = [f for f in result.findings if not f.waived]

    print_fn(f"jaxpr-audit: entry={entry} policy={policy}")
    print_fn(
        f"  traced {total} eqn(s); {len(upcasts)} reduced->wide "
        "convert_element_type op(s)"
    )
    bad = 0
    for file, line, old, new in upcasts:
        status, detail = _explain(file, line, old, new, result)
        where = f"{file}:{line}" if file else "<no source frame>"
        print_fn(f"  {where}: {old} -> {new} [{status}] {detail}")
        if status in ("finding", "unexplained"):
            bad += 1
    print_fn(
        f"  static dtype findings over {', '.join(str(p) for p in static_paths)}: "
        f"{len(unwaived_static)} unwaived, "
        f"{len(result.findings) - len(unwaived_static)} waived"
    )
    for f in unwaived_static:
        print_fn(f"  static: {f.file}:{f.line}: {f.rule}: {f.message}")
    ok = bad == 0 and not unwaived_static
    print_fn(f"jaxpr-audit: {'clean' if ok else 'NOT clean'}")
    return 0 if ok else 1
