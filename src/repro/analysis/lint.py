"""AST-based repo-policy linter.

Usage::

    python -m repro.analysis.lint src tests benchmarks examples

Exits 1 if any finding survives the inline allowlist, 0 on a clean
tree.  Suppress a genuinely intentional site with a same-line
annotation (a reason after ``--`` is encouraged)::

    from jax.experimental.pallas import tpu  # repro-lint: ignore[compat-import] -- the pin itself

Rules (see ROADMAP.md "Architecture reference" for the table):

``compat-import``
    ``jax.experimental.pallas.tpu`` may only be imported by
    ``kernels/compat.py`` — every kernel goes through the ``pltpu``
    proxy so version renames are absorbed in exactly one place.
``pltpu-api-surface``
    Files under ``kernels/`` may only touch ``pltpu.<name>`` for names
    the sibling ``compat.py`` explicitly pins (``_PltpuCompat`` class
    attributes); anything else would silently bypass the pin via the
    proxy's ``__getattr__`` fallthrough.
``donation-rebind``
    The result of a ``make_bulk_ingest_fn`` / ``make_scan_ingest_fn``
    factory is jitted with ``donate_argnums=0``: its first argument's
    buffer is invalid after the call.  Flag calls whose result is
    discarded, and reads of the donated variable before it is rebound.
    Failure-path corollary (not statically checkable, enforced by
    tests/test_bulk_ingest.py): when a donating call RAISES, the rebind
    never ran and the caller-visible state may hold already-deleted
    buffers.  Callers owning durable state must either leave it intact
    (failure before dispatch) or explicitly poison it —
    ``ActiveSegment``/``ShardedActiveSegment`` wrap the call and flip
    ``_poisoned`` when any state leaf ``is_deleted()``, so every later
    use raises at the cause instead of deep inside JAX.  Keep the check
    in a helper called from the ``except`` block: reading the donated
    name inline there would (correctly) trip this rule.
``host-sync-in-hot-path``
    Inside jitted / shard_mapped functions in ``core/`` and
    ``kernels/``: ``.item()``, ``.block_until_ready()``,
    ``np.asarray(...)``, and ``int()``/``float()`` of non-static values
    force a host sync (or a tracer error) — flag them.

Adding a rule: write a ``_rule_<name>(tree, ctx) -> Iterable[Finding]``
function and append it to ``_RULES``; the driver handles file walking,
allowlisting, and exit codes.
"""
from __future__ import annotations

import ast
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set

RULES = ("compat-import", "pltpu-api-surface", "donation-rebind",
         "host-sync-in-hot-path")

_IGNORE_RE = re.compile(r"#\s*repro-lint:\s*ignore\[([a-zA-Z,\s-]+)\]")

# Factories whose results are jitted with donate_argnums=0 (first arg
# donated).  make_scan_ingest_fn is reserved for the planned donating
# scan path; listing it now keeps the rule ahead of the code.
DONATING_FACTORIES = ("make_bulk_ingest_fn", "make_scan_ingest_fn")

# Fallback pin list if no sibling compat.py can be parsed (kept in sync
# with kernels/compat.py::_PltpuCompat by test_analysis.py).
FALLBACK_PINNED = frozenset({
    "MemorySpace", "ANY", "VMEM", "SMEM", "PrefetchScalarGridSpec",
    "SemaphoreType", "make_async_copy",
})

_STATIC_ATTRS = {"shape", "ndim", "size", "itemsize", "dtype"}
_STATIC_CALLS = {"len", "min", "max", "abs", "round", "sum"}


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}"


@dataclasses.dataclass
class _Ctx:
    """Per-file context handed to every rule."""
    path: Path
    in_kernels: bool
    in_core: bool
    is_compat: bool
    pinned: frozenset


def _dotted(node: ast.AST) -> Optional[str]:
    """'self.state' for one-or-two-level Name/Attribute chains."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        if base is not None:
            return f"{base}.{node.attr}"
    return None


def _ignored_lines(source: str) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _IGNORE_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def pinned_pltpu_names(compat_path: Path) -> frozenset:
    """Parse ``_PltpuCompat``'s class-attribute names out of compat.py."""
    try:
        tree = ast.parse(compat_path.read_text(), filename=str(compat_path))
    except (OSError, SyntaxError):
        return FALLBACK_PINNED
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "_PltpuCompat":
            names = set()
            for stmt in node.body:
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            names.add(t.id)
                elif isinstance(stmt, ast.AnnAssign) and isinstance(
                        stmt.target, ast.Name):
                    names.add(stmt.target.id)
            if names:
                return frozenset(names)
    return FALLBACK_PINNED


# --------------------------------------------------------------------------
# rule: compat-import
# --------------------------------------------------------------------------

def _rule_compat_import(tree: ast.AST, ctx: _Ctx) -> Iterable[Finding]:
    if ctx.is_compat:
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith("jax.experimental.pallas.tpu"):
                    yield Finding(
                        str(ctx.path), node.lineno, node.col_offset,
                        "compat-import",
                        "import jax.experimental.pallas.tpu only in "
                        "kernels/compat.py; use the pltpu proxy from "
                        "repro.kernels.compat")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            hits = mod.startswith("jax.experimental.pallas.tpu") or (
                mod == "jax.experimental.pallas"
                and any(a.name == "tpu" for a in node.names))
            if hits:
                yield Finding(
                    str(ctx.path), node.lineno, node.col_offset,
                    "compat-import",
                    "import jax.experimental.pallas.tpu only in "
                    "kernels/compat.py; use the pltpu proxy from "
                    "repro.kernels.compat")


# --------------------------------------------------------------------------
# rule: pltpu-api-surface
# --------------------------------------------------------------------------

def _rule_pltpu_surface(tree: ast.AST, ctx: _Ctx) -> Iterable[Finding]:
    if not ctx.in_kernels or ctx.is_compat:
        return
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "pltpu"
                and node.attr not in ctx.pinned):
            yield Finding(
                str(ctx.path), node.lineno, node.col_offset,
                "pltpu-api-surface",
                f"pltpu.{node.attr} is not pinned by kernels/compat.py "
                "(_PltpuCompat); pin it there before use so version "
                "renames stay absorbed in one place")


# --------------------------------------------------------------------------
# rule: donation-rebind
# --------------------------------------------------------------------------

def _mentions_any(node: ast.AST, names: Sequence[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in names:
            return True
    return False


def _assign_target_names(stmt: ast.stmt) -> List[str]:
    targets: List[ast.expr] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    out = []
    for t in targets:
        if isinstance(t, ast.Tuple):
            for elt in t.elts:
                d = _dotted(elt)
                if d:
                    out.append(d)
        else:
            d = _dotted(t)
            if d:
                out.append(d)
    return out


class _DonationScope:
    """Linear (source-order) donation analysis over one scope's body."""

    def __init__(self, ctx: _Ctx, ingest_fns: Set[str],
                 factories: Set[str]):
        self.ctx = ctx
        self.ingest_fns = set(ingest_fns)
        self.factories = set(factories)
        self.findings: List[Finding] = []

    def run(self, body: Sequence[ast.stmt]) -> List[Finding]:
        # Pass 1: collect aliases (factory aliases and ingest fns) so a
        # call above its alias's textual definition still resolves.
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                    continue
                value = node.value
                if value is None:
                    continue
                names = _assign_target_names(node)
                if not names:
                    continue
                if isinstance(value, ast.Call) and _mentions_any(
                        value.func, tuple(self.factories)):
                    self.ingest_fns.update(names)
                elif _mentions_any(value, tuple(self.factories)):
                    # e.g. make = (make_bulk_ingest_fn if bulk else ...)
                    self.factories.update(names)
        # Pass 2: find donating calls and use-after-donate reads.
        calls = []          # (lineno, col, stmt, call, donated_name)
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                fn = _dotted(node.func)
                if fn not in self.ingest_fns or not node.args:
                    continue
                donated = _dotted(node.args[0])
                calls.append((node.lineno, node.col_offset, stmt, node,
                              donated))
        for lineno, col, stmt, call, donated in calls:
            if isinstance(stmt, ast.Expr) and stmt.value is call:
                self.findings.append(Finding(
                    str(self.ctx.path), lineno, col, "donation-rebind",
                    "result of donating ingest call is discarded; the "
                    "donated input buffer is gone — rebind it: "
                    "state = ingest(state, ...)"))
                continue
            if donated is None:
                continue
            rebound_at = self._first_rebind_after(body, donated, lineno)
            read = self._first_read_after(body, donated, lineno,
                                          rebound_at)
            if read is not None:
                self.findings.append(Finding(
                    str(self.ctx.path), read[0], read[1],
                    "donation-rebind",
                    f"'{donated}' was donated to a donate_argnums=0 "
                    f"ingest fn at line {lineno} and is read again "
                    "before being rebound"))
        return self.findings

    def _first_rebind_after(self, body, name, lineno) -> Optional[int]:
        best = None
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign)):
                    # >= : `state = ingest(state, ...)` rebinds on the
                    # call's own line, which is the canonical pattern.
                    if node.lineno >= lineno and name in \
                            _assign_target_names(node):
                        if best is None or node.lineno < best:
                            best = node.lineno
        return best

    def _first_read_after(self, body, name, lineno, rebound_at):
        limit = rebound_at if rebound_at is not None else float("inf")
        best = None
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(node, "ctx", None), ast.Load):
                    if _dotted(node) == name and \
                            lineno < node.lineno < limit:
                        if best is None or node.lineno < best[0]:
                            best = (node.lineno, node.col_offset)
        return best


def _rule_donation_rebind(tree: ast.AST, ctx: _Ctx) -> Iterable[Finding]:
    findings: List[Finding] = []

    def scopes(node, inherited_ingest, inherited_factories):
        """Yield (body, ingest_fns, factories) per analysis scope."""
        if isinstance(node, ast.ClassDef):
            # Class-wide pass: self.X aliases assigned in any method are
            # visible to every other method (the ActiveSegment pattern).
            cls_ingest = set(inherited_ingest)
            cls_factories = set(inherited_factories)
            probe = _DonationScope(ctx, cls_ingest, cls_factories)
            for method in node.body:
                if isinstance(method, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    probe.ingest_fns = cls_ingest
                    probe.factories = cls_factories
                    probe.run(method.body)
                    cls_ingest |= {n for n in probe.ingest_fns
                                   if n.startswith("self.")}
                    cls_factories |= {n for n in probe.factories
                                      if n.startswith("self.")}
            for method in node.body:
                yield from scopes(method, cls_ingest, cls_factories)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.body, set(inherited_ingest), set(inherited_factories)
            for stmt in node.body:
                yield from scopes(stmt, inherited_ingest,
                                  inherited_factories)
        else:
            for child in ast.iter_child_nodes(node):
                yield from scopes(child, inherited_ingest,
                                  inherited_factories)

    findings.extend(_DonationScope(ctx, set(), set(DONATING_FACTORIES))
                    .run(getattr(tree, "body", [])))
    for body, ingest, factories in scopes(
            tree, set(), set(DONATING_FACTORIES)):
        sub = _DonationScope(ctx, ingest, factories)
        findings.extend(sub.run(body))
    seen = set()
    for f in findings:
        key = (f.line, f.col, f.message)
        if key not in seen:
            seen.add(key)
            yield f


# --------------------------------------------------------------------------
# rule: host-sync-in-hot-path
# --------------------------------------------------------------------------

def _is_static_expr(node: ast.AST) -> bool:
    """Conservatively true when int()/float() of it is trace-safe."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return True                      # bare python locals: assume static
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        # x.shape[0] is static; state.watermark[p] is a device gather.
        return isinstance(node.value, ast.Attribute) and \
            node.value.attr == "shape"
    if isinstance(node, ast.BinOp):
        return _is_static_expr(node.left) and _is_static_expr(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_static_expr(node.operand)
    if isinstance(node, ast.IfExp):
        return all(_is_static_expr(n)
                   for n in (node.test, node.body, node.orelse))
    if isinstance(node, ast.Call):
        if isinstance(node.func, ast.Name) and \
                node.func.id in _STATIC_CALLS:
            return True
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "bit_length":
            return True
    return False


def _is_jit_decorator(dec: ast.expr) -> bool:
    d = _dotted(dec)
    if d in ("jit", "jax.jit", "shard_map", "jax.experimental.shard_map"):
        return True
    if isinstance(dec, ast.Call):
        fn = _dotted(dec.func)
        if fn in ("jit", "jax.jit", "shard_map"):
            return True
        if fn in ("partial", "functools.partial"):
            return any(_dotted(a) in ("jit", "jax.jit", "shard_map")
                       for a in dec.args)
    return False


def _hot_functions(tree: ast.AST) -> List[ast.AST]:
    """Functions jitted by decorator or by a later jax.jit(name) call."""
    jitted_names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = _dotted(node.func)
            if fn in ("jax.jit", "jit", "shard_map") and node.args:
                d = _dotted(node.args[0])
                if d:
                    jitted_names.add(d)
            elif fn in ("partial", "functools.partial") and node.args:
                if _dotted(node.args[0]) in ("jax.jit", "jit"):
                    for extra in node.args[1:]:
                        d = _dotted(extra)
                        if d:
                            jitted_names.add(d)
    hot = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_decorator(d) for d in node.decorator_list) \
                    or node.name in jitted_names:
                hot.append(node)
    return hot


def _rule_host_sync(tree: ast.AST, ctx: _Ctx) -> Iterable[Finding]:
    if not (ctx.in_core or ctx.in_kernels):
        return
    for fn in _hot_functions(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = node.func
            if isinstance(callee, ast.Attribute):
                if callee.attr == "item" and not node.args:
                    yield Finding(
                        str(ctx.path), node.lineno, node.col_offset,
                        "host-sync-in-hot-path",
                        ".item() inside a jitted/shard_mapped function "
                        "forces a host sync (or a tracer error)")
                elif callee.attr == "block_until_ready":
                    yield Finding(
                        str(ctx.path), node.lineno, node.col_offset,
                        "host-sync-in-hot-path",
                        ".block_until_ready() inside a jitted function "
                        "is a host sync; hoist it out of the hot path")
                elif callee.attr == "asarray" and \
                        isinstance(callee.value, ast.Name) and \
                        callee.value.id in ("np", "numpy"):
                    yield Finding(
                        str(ctx.path), node.lineno, node.col_offset,
                        "host-sync-in-hot-path",
                        "np.asarray of a traced value inside a jitted "
                        "function devices-to-host copies; use jnp")
            elif isinstance(callee, ast.Name) and \
                    callee.id in ("int", "float") and len(node.args) == 1:
                if not _is_static_expr(node.args[0]):
                    yield Finding(
                        str(ctx.path), node.lineno, node.col_offset,
                        "host-sync-in-hot-path",
                        f"{callee.id}() of a (likely) traced value "
                        "inside a jitted function forces a host sync; "
                        "keep it a jnp scalar or hoist to the caller")


_RULES = (_rule_compat_import, _rule_pltpu_surface, _rule_donation_rebind,
          _rule_host_sync)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def _make_ctx(path: Path) -> _Ctx:
    parts = path.parts
    in_kernels = "kernels" in parts
    in_core = "core" in parts
    is_compat = in_kernels and path.name == "compat.py"
    pinned = FALLBACK_PINNED
    if in_kernels and not is_compat:
        sibling = path.parent / "compat.py"
        if sibling.exists():
            pinned = pinned_pltpu_names(sibling)
    return _Ctx(path=path, in_kernels=in_kernels, in_core=in_core,
                is_compat=is_compat, pinned=pinned)


def lint_source(source: str, path) -> List[Finding]:
    path = Path(path)
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as exc:
        return [Finding(str(path), exc.lineno or 0, exc.offset or 0,
                        "parse-error", f"syntax error: {exc.msg}")]
    ctx = _make_ctx(path)
    ignored = _ignored_lines(source)
    findings = []
    for rule in _RULES:
        for f in rule(tree, ctx):
            allow = ignored.get(f.line, ())
            if f.rule in allow or "all" in allow:
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_file(path) -> List[Finding]:
    path = Path(path)
    try:
        source = path.read_text()
    except OSError as exc:
        return [Finding(str(path), 0, 0, "parse-error",
                        f"unreadable: {exc}")]
    return lint_source(source, path)


def iter_python_files(paths: Sequence) -> Iterable[Path]:
    for p in paths:
        p = Path(p)
        if p.is_dir():
            yield from sorted(p.rglob("*.py"))
        elif p.suffix == ".py":
            yield p


def lint_paths(paths: Sequence) -> List[Finding]:
    findings: List[Finding] = []
    for f in iter_python_files(paths):
        findings.extend(lint_file(f))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print("usage: python -m repro.analysis.lint PATH [PATH ...]",
              file=sys.stderr)
        return 2
    findings = lint_paths(argv)
    for f in findings:
        print(f.render())
    if findings:
        print(f"repro-lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
