"""Repo-invariant AST lint (``repro.analysis``, DESIGN.md §8).

A small, deliberately non-configurable ``ast`` pass enforcing invariants
this repo has already been bitten by — each rule is named after the bug
class it prevents:

``accepted-kwarg-not-forwarded``
    A ``def`` accepts a named parameter that its body never reads or
    passes through.  This is the PR 4 bug class: ``precision=`` accepted
    by the MEC paths and silently dropped on the floor.  Parameters
    named ``self``/``cls``/``_*`` and pure interface stubs
    (``pass``/``...``/``raise NotImplementedError`` bodies) are exempt.

``raw-environ-read-outside-compat``
    ``os.environ[...]`` / ``os.environ.get`` / ``os.getenv`` read
    anywhere but ``core/compat.py``, the plan cache (``plan/cache.py``),
    and the calibration store (``plan/calibrate.py``).  Env reads are
    deployment surface; a few modules owning them keeps it auditable.

``deprecated-acc-bytes-env``
    Any read of the deprecated ``REPRO_MEC_ACC_BYTES`` override outside
    its one sanctioned accessor; tuned accumulator budgets belong in a
    :class:`repro.plan.ConvPlan`.

``no-bare-dot-precision``
    A ``jnp.dot`` / ``jnp.einsum`` / ``lax.dot_general`` (any attribute
    call named ``dot``/``einsum``/``dot_general``) inside the numeric
    core (``src/repro/core``, ``src/repro/kernels``,
    ``src/repro/parallel``) without an explicit ``precision=`` or
    ``preferred_element_type=`` keyword.  A bare GEMM silently runs at
    the backend default — the exact silent-downcast class the
    shardcheck precision-flow pass catches after lowering; this rule
    catches it at the call site.

Suppression: append ``# lint-ignore: <rule>[, <rule>...]`` (or a bare
``# lint-ignore`` for every rule) to the flagged line — for the kwarg
rule, to the ``def`` line.  Pre-existing findings are grandfathered in a
committed baseline (``benchmarks/baselines/lint_baseline.json``) keyed
by ``rule:path:symbol`` — line-number free, so unrelated edits never
churn it.  Any finding not in the baseline fails the run; fixing a
grandfathered finding and regenerating (``python -m repro.analysis
--suite lint --update-lint-baseline``) shrinks the baseline
monotonically.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

LINT_BASELINE_VERSION = 1

RULES = (
    "accepted-kwarg-not-forwarded",
    "raw-environ-read-outside-compat",
    "deprecated-acc-bytes-env",
    "no-bare-dot-precision",
)

# Directories whose GEMM call sites must pin their numerics (the rule
# scope, not the scan scope — bench/examples glue may use defaults).
# PR 10 widened the scope from the numeric core to everything that
# executes on the serving/training path and burned the grandfathered
# baseline to zero — new findings fail outright now.
_DOT_PRECISION_DIRS = ("src/repro/core/", "src/repro/kernels/",
                       "src/repro/parallel/", "src/repro/models/",
                       "src/repro/serving/", "src/repro/plan/")
_DOT_CALLEES = ("dot", "einsum", "dot_general")

# Files allowed to read the environment raw: the startup environment
# module and the plan cache + calibration store (whose directory/file
# overrides ARE their public configuration).
_ENVIRON_ALLOWED = ("core/compat.py", "plan/cache.py", "plan/calibrate.py")
_ACC_BYTES_ENV = "REPRO_MEC_ACC_BYTES"

# Directories scanned relative to the repo root; tests are out of scope
# (fixtures deliberately contain violations).
DEFAULT_SCAN_DIRS = ("src/repro", "benchmarks", "examples")

_SUPPRESS_RE = re.compile(r"#\s*lint-ignore(?::\s*(?P<rules>[\w\-, ]+))?")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint violation.  ``key()`` is the line-stable identity the
    baseline stores: rule + file + symbol, never the line number."""

    rule: str
    path: str                  # repo-relative, forward slashes
    symbol: str                # enclosing def/import detail
    lineno: int
    message: str

    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.symbol}"

    def render(self) -> str:
        return f"{self.path}:{self.lineno}: [{self.rule}] {self.message}"


def _suppressed(source_lines: Sequence[str], lineno: int,
                rule: str) -> bool:
    if not 1 <= lineno <= len(source_lines):
        return False
    m = _SUPPRESS_RE.search(source_lines[lineno - 1])
    if not m:
        return False
    rules = m.group("rules")
    if rules is None:
        return True
    return rule in {r.strip() for r in rules.split(",")}


def _is_stub_body(body: Sequence[ast.stmt]) -> bool:
    """Interface stubs legitimately ignore their parameters."""
    stmts = list(body)
    if stmts and isinstance(stmts[0], ast.Expr) and \
            isinstance(stmts[0].value, ast.Constant) and \
            isinstance(stmts[0].value.value, str):
        stmts = stmts[1:]                      # docstring
    if not stmts:
        return True
    if len(stmts) > 1:
        return False
    s = stmts[0]
    if isinstance(s, ast.Pass):
        return True
    if isinstance(s, ast.Expr) and isinstance(s.value, ast.Constant) \
            and s.value.value is Ellipsis:
        return True
    if isinstance(s, ast.Raise) and s.exc is not None:
        name = s.exc.func if isinstance(s.exc, ast.Call) else s.exc
        return getattr(name, "id", None) == "NotImplementedError"
    return False


def _check_unused_params(tree: ast.AST, path: str,
                         lines: Sequence[str]) -> List[Finding]:
    rule = "accepted-kwarg-not-forwarded"
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if any(isinstance(d, ast.Name) and d.id in ("overload",)
               for d in node.decorator_list):
            continue
        if _is_stub_body(node.body):
            continue
        args = node.args
        params = [a.arg for a in (args.posonlyargs + args.args
                                  + args.kwonlyargs)]
        names_read = {n.id for stmt in node.body
                      for n in ast.walk(stmt) if isinstance(n, ast.Name)}
        # A nested def/lambda re-binding the name still counts via Name
        # nodes; ``**kwargs`` forwarding reads the kwargs Name itself.
        for p in params:
            if p in ("self", "cls") or p.startswith("_"):
                continue
            if p in names_read:
                continue
            if _suppressed(lines, node.lineno, rule):
                continue
            out.append(Finding(
                rule=rule, path=path, symbol=f"{node.name}:{p}",
                lineno=node.lineno,
                message=f"def {node.name}(...) accepts {p!r} but its body "
                        f"never reads or forwards it (PR-4 dropped-kwarg "
                        f"class)"))
    return out


def _environ_read_calls(tree: ast.AST) -> Iterable[Tuple[ast.AST, str,
                                                         Optional[ast.expr]]]:
    """Yield (node, kind, key_expr) for every raw environment *read*:
    ``os.environ.get/setdefault(k)``, ``os.environ[k]`` loads, and
    ``os.getenv(k)``.  Writes (``os.environ[k] = v``) are not reads."""
    def is_os_environ(n: ast.AST) -> bool:
        return (isinstance(n, ast.Attribute) and n.attr == "environ"
                and isinstance(n.value, ast.Name) and n.value.id == "os")

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and \
                    f.attr in ("get", "setdefault") and \
                    is_os_environ(f.value):
                yield node, f"os.environ.{f.attr}", \
                    node.args[0] if node.args else None
            elif isinstance(f, ast.Attribute) and f.attr == "getenv" and \
                    isinstance(f.value, ast.Name) and f.value.id == "os":
                yield node, "os.getenv", node.args[0] if node.args else None
        elif isinstance(node, ast.Subscript) and \
                is_os_environ(node.value) and \
                isinstance(node.ctx, ast.Load):
            yield node, "os.environ[...]", node.slice


def _check_environ_reads(tree: ast.AST, path: str,
                         lines: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    allowed = any(path.endswith(a) for a in _ENVIRON_ALLOWED)
    for node, kind, key in _environ_read_calls(tree):
        key_name = None
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            key_name = key.value
        elif isinstance(key, ast.Name):
            key_name = key.id
        deprecated = key_name in (_ACC_BYTES_ENV, "ACC_BYTES_ENV")
        if not allowed and not _suppressed(
                lines, node.lineno, "raw-environ-read-outside-compat"):
            out.append(Finding(
                rule="raw-environ-read-outside-compat", path=path,
                symbol=f"{kind}:{key_name or '<dynamic>'}",
                lineno=node.lineno,
                message=f"{kind}({key_name or '...'}) outside "
                        f"{_ENVIRON_ALLOWED}: route environment surface "
                        f"through repro.core.compat or the plan cache"))
        if deprecated and not _suppressed(
                lines, node.lineno, "deprecated-acc-bytes-env"):
            out.append(Finding(
                rule="deprecated-acc-bytes-env", path=path,
                symbol=f"{kind}:{key_name}", lineno=node.lineno,
                message=f"read of deprecated {_ACC_BYTES_ENV}: tuned "
                        f"accumulator budgets belong in a ConvPlan "
                        f"(repro.plan.plan_conv2d -> plan.w_blk)"))
    return out


def _check_bare_dot_precision(tree: ast.AST, path: str,
                              lines: Sequence[str]) -> List[Finding]:
    rule = "no-bare-dot-precision"
    if not any(path.startswith(d) for d in _DOT_PRECISION_DIRS):
        return []
    out: List[Finding] = []

    def visit(node: ast.AST, scope: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit(child, child.name)
                continue
            if isinstance(child, ast.Call):
                f = child.func
                kws = {k.arg for k in child.keywords}
                if isinstance(f, ast.Attribute) \
                        and f.attr in _DOT_CALLEES \
                        and "precision" not in kws \
                        and "preferred_element_type" not in kws \
                        and None not in kws \
                        and not _suppressed(lines, child.lineno, rule):
                    # a **kwargs splat (None in kws) may carry
                    # precision; shardcheck's flow pass still audits
                    # what actually lowers.
                    base = getattr(f.value, "id",
                                   getattr(f.value, "attr", "?"))
                    out.append(Finding(
                        rule=rule, path=path,
                        symbol=f"{scope}:{base}.{f.attr}",
                        lineno=child.lineno,
                        message=f"{base}.{f.attr}(...) in {scope} without "
                                f"explicit precision= or "
                                f"preferred_element_type= — a bare GEMM "
                                f"runs at the backend default "
                                f"(silent-downcast class; see "
                                f"shardcheck's precision-flow pass)"))
            visit(child, scope)

    visit(tree, "<module>")
    return out


def lint_file(path: pathlib.Path, rel: str) -> List[Finding]:
    source = path.read_text()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [Finding(rule="accepted-kwarg-not-forwarded", path=rel,
                        symbol="<syntax-error>", lineno=e.lineno or 0,
                        message=f"file does not parse: {e.msg}")]
    lines = source.splitlines()
    out: List[Finding] = []
    out += _check_unused_params(tree, rel, lines)
    out += _check_environ_reads(tree, rel, lines)
    out += _check_bare_dot_precision(tree, rel, lines)
    return out


def repo_root() -> pathlib.Path:
    """The checkout root (three levels above this file's package)."""
    return pathlib.Path(__file__).resolve().parents[3]


def lint_tree(root: Optional[pathlib.Path] = None,
              scan_dirs: Sequence[str] = DEFAULT_SCAN_DIRS) -> List[Finding]:
    root = pathlib.Path(root) if root is not None else repo_root()
    findings: List[Finding] = []
    for d in scan_dirs:
        base = root / d
        if not base.exists():
            continue
        for py in sorted(base.rglob("*.py")):
            rel = py.relative_to(root).as_posix()
            findings.extend(lint_file(py, rel))
    return sorted(findings, key=lambda f: (f.path, f.lineno, f.rule))


# ---------------------------------------------------------------- baseline

def load_baseline(path) -> List[str]:
    doc = json.loads(pathlib.Path(path).read_text())
    if doc.get("lint_baseline_version") != LINT_BASELINE_VERSION:
        raise ValueError(
            f"lint baseline {path} has version "
            f"{doc.get('lint_baseline_version')!r}, expected "
            f"{LINT_BASELINE_VERSION}")
    keys = doc.get("findings")
    if not isinstance(keys, list) or \
            not all(isinstance(k, str) for k in keys):
        raise ValueError(f"lint baseline {path}: findings must be a list "
                         "of rule:path:symbol strings")
    return keys


def write_baseline(findings: Sequence[Finding], path) -> None:
    doc = {
        "lint_baseline_version": LINT_BASELINE_VERSION,
        "findings": sorted({f.key() for f in findings}),
    }
    pathlib.Path(path).write_text(
        json.dumps(doc, indent=2, sort_keys=True) + "\n")


def apply_baseline(findings: Sequence[Finding],
                   baseline_keys: Sequence[str]) -> Dict[str, List]:
    """Split findings into new failures vs. grandfathered, and report
    baseline entries that no longer fire (fixed — shrink the file)."""
    baseline = set(baseline_keys)
    new = [f for f in findings if f.key() not in baseline]
    grandfathered = [f for f in findings if f.key() in baseline]
    fixed = sorted(baseline - {f.key() for f in findings})
    return {"new": new, "grandfathered": grandfathered, "fixed": fixed}
