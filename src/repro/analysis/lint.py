"""JAX lint: AST rules flagging host-sync and retracing hazards in traced
scopes.

The repo's hot paths are jitted scan/vmap bodies; a stray ``float()`` or
``np.asarray`` inside one silently drags the whole value back to host every
dispatch (or fails only at trace time on an obscure input), and a Python
``if`` on a tracer raises a ConcretizationTypeError three layers away from
the actual mistake. This pass finds those *lexically*, before anything
runs.

Scope detection — a function is considered **traced** when any of:

  * it is decorated with ``jax.jit`` / ``functools.partial(jax.jit, …)`` /
    ``jax.checkpoint`` / ``jax.remat``;
  * it (or a ``partial(fn, …)`` / plain alias of it) is passed to a JAX
    tracing entry point: ``jit``, ``vmap``, ``pmap``, ``grad``,
    ``value_and_grad``, ``lax.scan``, ``fori_loop``, ``while_loop``,
    ``cond``, ``switch``, ``associative_scan``, ``lax.map``,
    ``pallas_call``, ``shard_map``, ``eval_shape``, ``make_jaxpr``;
  * it is lexically nested inside a traced function (scan bodies, helper
    closures);
  * it is referenced by name from inside a traced function in the same
    module (one-module call-graph closure — catches ``simulate_one``
    called by the vmap lambda in ``simulate_batch``);
  * its ``def`` line carries the explicit marker comment
    ``# repro: traced`` — for functions whose tracing caller lives in a
    *different* module (``chain.resimulate_chains`` is traced by the
    chain block in ``core.device_explore``), where no static analysis of
    this file can see the jit.

Rules (ids are what ``# repro: noqa[<rule>]`` must name):

  ``host-sync``        ``float()``/``int()``/``bool()`` on a non-literal,
                       ``.item()``, ``np.asarray``/``np.array``,
                       ``jax.device_get``, ``.block_until_ready()`` inside
                       a traced scope — each forces a device→host transfer
                       per call (or a trace error).
  ``tracer-branch``    Python ``if``/``while``/``assert``/ternary whose
                       test involves a ``jnp.``/``lax.`` expression or an
                       ``.any()``/``.all()`` reduction — control flow on a
                       tracer concretizes; use ``jnp.where``/``lax.cond``.
  ``f64-promote``      ``math.*`` calls, ``np.float64``, or a ``float64``
                       dtype inside a traced scope — ``math`` concretizes
                       the tracer and returns a Python float; np.float64
                       operands promote f32 pipelines to f64.
  ``mutable-closure``  mutating a free (closed-over) variable inside a
                       traced scope — ``xs.append(…)``, ``cache[k] = v``,
                       ``x += …`` on names the function does not bind, and
                       ``global``/``nonlocal`` — the mutation runs once at
                       trace time, not per call, and is invisible to the
                       jit cache key.
  ``noqa-reason``      a ``# repro: noqa[…]`` with no justification text —
                       suppressions must say why.

Suppression: append ``# repro: noqa[rule]: reason`` to the offending line.
Existing debt is frozen (not hidden) in the checked-in baseline
(``src/repro/analysis/baseline.json``, keyed on file+rule+line-text so it
survives line drift); ``python -m repro.analysis --update-baseline``
regenerates it, ``--strict`` fails on anything new.
"""
from __future__ import annotations

import ast
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .findings import Finding

__all__ = [
    "RULES",
    "lint_source",
    "lint_paths",
    "run_lint",
    "load_baseline",
    "apply_baseline",
    "write_baseline",
    "default_baseline_path",
    "default_lint_root",
]

RULES = {
    "host-sync": "device→host transfer inside a traced scope",
    "tracer-branch": "Python control flow on a traced boolean",
    "f64-promote": "f64-promoting host math inside a traced scope",
    "mutable-closure": "closed-over mutable state mutated in a traced scope",
    "noqa-reason": "suppression without a justification string",
}

# names that take a function and trace it (matched on the LAST attribute
# segment, so jax.jit / jax.lax.scan / pl.pallas_call all hit)
_TRACE_ENTRY_NAMES = {
    "jit", "vmap", "pmap", "grad", "value_and_grad", "scan", "fori_loop",
    "while_loop", "cond", "switch", "associative_scan", "pallas_call",
    "shard_map", "eval_shape", "make_jaxpr", "checkpoint", "remat",
}
# lax.map is tracing too, but bare "map" would catch the builtin — require
# an attribute access for it
_TRACE_ATTR_ONLY = {"map"}

_NOQA_RE = re.compile(
    r"#\s*repro:\s*noqa\[([A-Za-z0-9_,\- ]+)\]\s*:?\s*(.*)$"
)
_TRACED_MARK_RE = re.compile(r"#\s*repro:\s*traced\b")

_FuncNode = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _last_name(node: ast.expr) -> Optional[str]:
    """Trailing identifier of a Name/Attribute chain (``jax.lax.scan`` →
    ``scan``), or None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _root_name(node: ast.expr) -> Optional[str]:
    """Leading identifier of an Attribute chain (``np.asarray`` → ``np``)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


class _Scopes:
    """Lexical function-scope index of one module: parents, name tables,
    and simple aliases (``f = g`` / ``f = partial(g, …)``)."""

    def __init__(self, tree: ast.Module) -> None:
        self.parent: Dict[ast.AST, Optional[ast.AST]] = {tree: None}
        self.defs: List[ast.AST] = []
        # (enclosing scope node, name) -> def node
        self.by_name: Dict[Tuple[ast.AST, str], ast.AST] = {}
        self.tree = tree
        stack: List[ast.AST] = [tree]

        def walk(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
                if isinstance(child, _FuncNode):
                    self.defs.append(child)
                    if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        self.by_name[(stack[-1], child.name)] = child
                    stack.append(child)
                    walk(child)
                    stack.pop()
                else:
                    if isinstance(child, ast.Assign) and len(child.targets) == 1:
                        t = child.targets[0]
                        v = child.value
                        alias = None
                        if isinstance(v, ast.Name):
                            alias = v.id
                        elif (
                            isinstance(v, ast.Call)
                            and _last_name(v.func) == "partial"
                            and v.args
                            and isinstance(v.args[0], ast.Name)
                        ):
                            alias = v.args[0].id
                        if alias is not None and isinstance(t, ast.Name):
                            self.by_name.setdefault((stack[-1], t.name
                                                     if hasattr(t, "name")
                                                     else t.id), None)
                            # map the alias target name onto the aliased def
                            # lazily: store the *name* and resolve later
                            self.by_name[(stack[-1], t.id)] = self.by_name.get(
                                (stack[-1], alias)
                            ) or self._resolve_from(stack[-1], alias)
                    walk(child)

        walk(tree)

    def scope_of(self, node: ast.AST) -> ast.AST:
        """Nearest enclosing function scope (or the module)."""
        cur = self.parent.get(node)
        while cur is not None and not isinstance(cur, _FuncNode):
            cur = self.parent.get(cur)
        return cur if cur is not None else self.tree

    def _resolve_from(self, scope: ast.AST, name: str) -> Optional[ast.AST]:
        cur: Optional[ast.AST] = scope
        while cur is not None:
            hit = self.by_name.get((cur, name))
            if hit is not None:
                return hit
            cur = self.parent.get(cur)
            while cur is not None and not isinstance(
                cur, _FuncNode + (ast.Module,)
            ):
                cur = self.parent.get(cur)
        return None

    def resolve(self, at: ast.AST, name: str) -> Optional[ast.AST]:
        """Find the def a Name load refers to, walking scopes outward."""
        return self._resolve_from(self.scope_of(at), name)


def _traced_defs(tree: ast.Module, scopes: _Scopes,
                 lines: List[str]) -> Set[ast.AST]:
    traced: Set[ast.AST] = set()

    # 1. decorator-marked + explicit `# repro: traced` marker
    for d in scopes.defs:
        if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in d.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = _last_name(target)
                if name == "partial" and isinstance(dec, ast.Call) and dec.args:
                    name = _last_name(dec.args[0])
                if name in _TRACE_ENTRY_NAMES:
                    traced.add(d)
            ln = d.lineno - 1
            if 0 <= ln < len(lines) and _TRACED_MARK_RE.search(lines[ln]):
                traced.add(d)

    # 2. functions handed to tracing entry points
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _last_name(node.func)
        is_entry = name in _TRACE_ENTRY_NAMES or (
            name in _TRACE_ATTR_ONLY and isinstance(node.func, ast.Attribute)
        )
        if not is_entry:
            continue
        for arg in node.args:
            if isinstance(arg, ast.Lambda):
                traced.add(arg)
            elif isinstance(arg, ast.Name):
                hit = scopes.resolve(node, arg.id)
                if hit is not None:
                    traced.add(hit)
            elif (
                isinstance(arg, ast.Call)
                and _last_name(arg.func) == "partial"
                and arg.args
                and isinstance(arg.args[0], ast.Name)
            ):
                hit = scopes.resolve(node, arg.args[0].id)
                if hit is not None:
                    traced.add(hit)

    # 3. closure: lexical nesting + same-module references from traced code
    changed = True
    while changed:
        changed = False
        for d in scopes.defs:
            if d in traced:
                continue
            cur = scopes.parent.get(d)
            while cur is not None:
                if cur in traced:
                    traced.add(d)
                    changed = True
                    break
                cur = scopes.parent.get(cur)
        for d in list(traced):
            for node in ast.walk(d):
                if isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load
                ):
                    hit = scopes.resolve(node, node.id)
                    if hit is not None and hit not in traced:
                        # don't re-enter through the def currently walked
                        traced.add(hit)
                        changed = True
    return traced


def _local_bindings(fn: ast.AST) -> Set[str]:
    """Names a function binds itself: params + any Store/target inside it
    (excluding nested function bodies — those have their own scopes)."""
    names: Set[str] = set()
    args = fn.args
    for a in (
        list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
    ):
        names.add(a.arg)
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)

    def walk(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FuncNode):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    names.add(child.name)
                continue
            if isinstance(child, ast.Name) and isinstance(
                child.ctx, (ast.Store, ast.Del)
            ):
                names.add(child.id)
            walk(child)

    if isinstance(fn, ast.Lambda):
        return names
    for stmt in fn.body:
        walk(stmt)
        if isinstance(stmt, ast.Name) and isinstance(stmt.ctx, ast.Store):
            names.add(stmt.id)
    return names


_MUTATOR_METHODS = {
    "append", "extend", "insert", "update", "add", "setdefault", "pop",
    "popitem", "remove", "discard", "clear", "appendleft", "extendleft",
}
_NP_HOST_FNS = {"asarray", "array", "copy", "save", "savez"}
_NP_ROOTS = {"np", "numpy", "onp"}


def _contains_tracerish(node: ast.expr) -> bool:
    """Does an expression subtree smell like a traced array? Narrow on
    purpose: `jnp.`/`lax.`-rooted CALLS and `.any()`/`.all()` reductions.
    Static-config branches (`if menu == "farsi"`, `if n_noc == 1`) and
    dtype comparisons against `jnp.float32` must stay legal inside traced
    functions — a bare jnp attribute is a constant, only invoking one
    produces an array."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        if _root_name(sub.func) in ("jnp", "lax"):
            return True
        if (
            isinstance(sub.func, ast.Attribute)
            and sub.func.attr in ("any", "all")
            and not sub.args
        ):
            return True
    return False


def _is_f64_dtype(node: ast.expr) -> bool:
    if isinstance(node, ast.Constant) and node.value in (
        "float64", "double"
    ):
        return True
    return _last_name(node) in ("float64", "double")


def _lint_traced_fn(
    fn: ast.AST, path: str, lines: List[str], out: List[Finding]
) -> None:
    free_guard = _local_bindings(fn)

    def emit(rule: str, node: ast.AST, msg: str) -> None:
        line = getattr(node, "lineno", 0)
        src = lines[line - 1].strip() if 0 < line <= len(lines) else ""
        out.append(Finding(
            pass_name="lint", rule=rule, message=msg, path=path,
            line=line, source=src,
        ))

    body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
    for stmt in body if isinstance(body, list) else [body]:
        for node in ast.walk(stmt):
            if isinstance(node, _FuncNode):
                # nested defs are linted as their own traced scopes
                continue
            if isinstance(node, ast.Call):
                fname = _last_name(node.func)
                root = (
                    _root_name(node.func)
                    if isinstance(node.func, ast.Attribute) else None
                )
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and node.args
                    and not isinstance(node.args[0], ast.Constant)
                ):
                    emit("host-sync", node,
                         f"`{node.func.id}()` on a traced value forces a "
                         "device→host sync (use jnp casts / keep it "
                         "device-side)")
                elif fname == "item" and not node.args and isinstance(
                    node.func, ast.Attribute
                ):
                    emit("host-sync", node,
                         "`.item()` pulls the value to host inside a "
                         "traced scope")
                elif fname == "block_until_ready" and isinstance(
                    node.func, ast.Attribute
                ):
                    emit("host-sync", node,
                         "`.block_until_ready()` is a host sync — it has "
                         "no place inside a traced scope")
                elif root in _NP_ROOTS and fname in _NP_HOST_FNS:
                    emit("host-sync", node,
                         f"`{root}.{fname}` materializes the tracer on "
                         "host — use jnp inside traced code")
                elif root == "jax" and fname == "device_get":
                    emit("host-sync", node,
                         "`jax.device_get` inside a traced scope is a "
                         "per-call host transfer")
                elif root == "math":
                    emit("f64-promote", node,
                         f"`math.{fname}` concretizes the tracer and "
                         "returns a Python float (f64) — use jnp")
                elif root in _NP_ROOTS and fname == "float64":
                    emit("f64-promote", node,
                         "np.float64 operands promote the f32 pipeline "
                         "to f64")
                elif fname in _MUTATOR_METHODS and isinstance(
                    node.func, ast.Attribute
                ) and isinstance(node.func.value, ast.Name):
                    target = node.func.value.id
                    if target not in free_guard:
                        emit("mutable-closure", node,
                             f"`{target}.{fname}(…)` mutates closed-over "
                             "state at trace time — it will NOT re-run "
                             "per call and is invisible to the jit cache "
                             "key")
                for kw in node.keywords:
                    if kw.arg == "dtype" and _is_f64_dtype(kw.value):
                        emit("f64-promote", node,
                             "explicit float64 dtype inside a traced "
                             "scope")
            elif isinstance(node, (ast.If, ast.While, ast.IfExp, ast.Assert)):
                test = node.test
                if _contains_tracerish(test):
                    emit("tracer-branch", node,
                         "Python control flow on a traced boolean "
                         "concretizes — use jnp.where / lax.cond / "
                         "lax.select")
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                emit("mutable-closure", node,
                     f"`{'global' if isinstance(node, ast.Global) else 'nonlocal'}`"
                     " write-through inside a traced scope runs at trace "
                     "time only")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for t in targets:
                    if isinstance(t, ast.Subscript) and isinstance(
                        t.value, ast.Name
                    ):
                        target = t.value.id
                        # `o_ref[...] = acc` on a closed-over Pallas Ref is
                        # THE kernel output idiom, not trace-time leakage —
                        # Refs are mutable on device by design
                        if target not in free_guard and not target.endswith(
                            "_ref"
                        ):
                            emit("mutable-closure", node,
                                 f"subscript store into closed-over "
                                 f"`{target}` runs once at trace time, "
                                 "not per call")


def _noqa_filter(
    findings: List[Finding], lines: List[str], path: str
) -> List[Finding]:
    """Apply per-line `# repro: noqa[rule]` suppressions; a suppression
    with no reason text surfaces as its own ``noqa-reason`` finding."""
    out: List[Finding] = []
    reason_flagged: Set[int] = set()
    for f in findings:
        line_txt = lines[f.line - 1] if 0 < f.line <= len(lines) else ""
        m = _NOQA_RE.search(line_txt)
        if m:
            named = {r.strip() for r in m.group(1).split(",")}
            if f.rule in named or "*" in named:
                f = Finding(**{**f.__dict__, "suppressed": True})
                if not m.group(2).strip() and f.line not in reason_flagged:
                    reason_flagged.add(f.line)
                    out.append(Finding(
                        pass_name="lint", rule="noqa-reason",
                        message="suppression has no justification — add "
                        "`# repro: noqa[rule]: <why>`",
                        path=path, line=f.line, source=line_txt.strip(),
                    ))
        out.append(f)
    return out


def lint_source(src: str, path: str = "<memory>") -> List[Finding]:
    """Lint one module's source text. The unit tests drive this directly
    with fixture snippets; :func:`lint_paths` feeds it files."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(
            pass_name="lint", rule="host-sync",
            message=f"unparseable module: {e}", path=path,
            line=e.lineno or 0,
        )]
    lines = src.splitlines()
    scopes = _Scopes(tree)
    traced = _traced_defs(tree, scopes, lines)
    findings: List[Finding] = []
    for fn in traced:
        _lint_traced_fn(fn, path, lines, findings)
    # a (line, rule) can be reached through several traced parents after
    # the call-graph closure — report it once
    seen: Set[Tuple[int, str, str]] = set()
    deduped = []
    for f in sorted(findings, key=lambda f: (f.line, f.rule)):
        k = (f.line, f.rule, f.message)
        if k in seen:
            continue
        seen.add(k)
        deduped.append(f)
    return _noqa_filter(deduped, lines, path)


def default_lint_root() -> str:
    """``src/repro`` as shipped: the parent of this package."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def _rel(path: str, root: str) -> str:
    # stable repo-relative keys: src/repro/… regardless of install layout
    rp = os.path.relpath(path, os.path.dirname(os.path.dirname(root)))
    return rp.replace(os.sep, "/")


def lint_paths(paths: Iterable[str], root: Optional[str] = None) -> List[Finding]:
    root = root or default_lint_root()
    out: List[Finding] = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as fh:
            src = fh.read()
        out.extend(lint_source(src, path=_rel(p, root)))
    return out


def run_lint(root: Optional[str] = None) -> List[Finding]:
    """Lint every ``.py`` under ``src/repro/`` (excluding this package —
    the analyzer's own fixtures would trip the rules)."""
    root = root or default_lint_root()
    files: List[str] = []
    skip_dir = os.path.join(root, "analysis")
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        if os.path.abspath(dirpath).startswith(os.path.abspath(skip_dir)):
            continue
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                files.append(os.path.join(dirpath, fn))
    return lint_paths(sorted(files), root=root)


# ---------------------------------------------------------------------------
# baseline: freeze existing debt without hiding it
# ---------------------------------------------------------------------------
def load_baseline(path: Optional[str] = None) -> Dict[str, int]:
    path = path or default_baseline_path()
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return {str(k): int(v) for k, v in data.get("findings", {}).items()}


def apply_baseline(
    findings: List[Finding], baseline: Dict[str, int]
) -> List[Finding]:
    """Mark up to ``baseline[key]`` occurrences of each key as baselined
    (never suppressed ones — those are already accounted for)."""
    budget = dict(baseline)
    out: List[Finding] = []
    for f in findings:
        if not f.suppressed and budget.get(f.key(), 0) > 0:
            budget[f.key()] -= 1
            f = Finding(**{**f.__dict__, "baselined": True})
        out.append(f)
    return out


def write_baseline(
    findings: List[Finding], path: Optional[str] = None
) -> str:
    path = path or default_baseline_path()
    counts: Dict[str, int] = {}
    for f in findings:
        if f.suppressed or f.rule == "noqa-reason":
            continue
        counts[f.key()] = counts.get(f.key(), 0) + 1
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(
            {"comment": "frozen lint debt — python -m repro.analysis "
             "--update-baseline regenerates; tier-1 asserts this stays "
             "EMPTY for src/repro/core/",
             "findings": dict(sorted(counts.items()))},
            fh, indent=1, sort_keys=False,
        )
        fh.write("\n")
    return path
