"""Checker 2 — trace-purity lint.

The fused device program must be a pure function of its inputs: jitted
code (``jax.jit``/``pmap``/``shard_map`` roots and everything reachable
from them through the package call graph) runs once at TRACE time, so a
wall-clock read, RNG draw, or global mutation silently bakes a
trace-time value into the compiled program — the bug class that
produces "works once, wrong forever" — and host-level branching on a
traced value triggers a recompile per distinct value.

Rules:

* **TP01** — function reachable from a jit root calls a wall-clock /
  RNG / environment primitive (``time.*``, ``random.*``,
  ``np.random.*``, ``secrets.*``, ``os.environ``/``os.urandom``,
  ``datetime.now``, ``uuid.*``).
* **TP02** — a jit ROOT function branches (``if``/``while``) on one of
  its own parameters: Python-level control flow on a traced value is a
  recompile hazard (use ``jnp.where``/``lax.cond``). Checked on roots
  only — deeper helpers legitimately branch on host-side structure
  (IR nodes, schema metadata) at trace time.
* **TP03** — device sync (``jax.device_get`` / ``.block_until_ready``)
  outside the ``_device_fetch``/``_device_call`` choke points (package-
  wide: every result fetch must flow through the instrumented funnel
  that feeds the failpoints and the circuit breaker). ``warmup``
  methods are exempt — boot-time compilation priming blocks by design.
* **TP04** — function reachable from a jit root mutates module state
  (``global`` declaration).

Reachability is name-based (same resolution policy as the concurrency
checker): an over-approximation is fine — a flagged helper either gets
fixed or explicitly baselined.
"""

from __future__ import annotations

import ast
from pathlib import Path

from tools.graftcheck.base import Finding, iter_py_files, resolve_callee

_JIT_WRAPPERS = {"jit", "pmap", "shard_map"}
# _compile_columns: the off-path plane compiler blocks on the program it
# just compiled by design (same exemption rationale as warmup)
_SYNC_CHOKE_POINTS = {
    "_device_fetch", "_device_call", "warmup", "_compile_columns",
}
_BANNED_PREFIXES = (
    "time.time",
    "time.time_ns",
    "time.monotonic",
    "time.perf_counter",
    "time.sleep",
    "random.",
    "np.random.",
    "numpy.random.",
    "secrets.",
    "os.urandom",
    "os.environ",
    "os.getenv",
    "datetime.now",
    "datetime.utcnow",
    "datetime.datetime.now",
    "uuid.",
)


def _dotted(expr: ast.expr) -> str | None:
    parts: list[str] = []
    node = expr
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Func:
    def __init__(
        self,
        relpath: str,
        cls: str | None,
        node: ast.FunctionDef | ast.AsyncFunctionDef,
    ):
        self.relpath = relpath
        self.cls = cls
        self.node = node
        self.name = node.name
        self.key = f"{relpath}::{(cls + '.') if cls else ''}{node.name}"
        self.calls: list[tuple[str, str]] = []  # (kind, name)
        self.banned: list[tuple[str, int]] = []  # (dotted name, line)
        self.globals: list[int] = []
        self.syncs: list[tuple[str, int]] = []
        self.param_branches: list[tuple[str, int]] = []
        self._analyze()

    def _analyze(self) -> None:
        params = {
            a.arg
            for a in (
                self.node.args.posonlyargs
                + self.node.args.args
                + self.node.args.kwonlyargs
            )
            if a.arg not in ("self", "cls")
        }
        for sub in ast.walk(self.node):
            if isinstance(sub, ast.Call):
                dotted = _dotted(sub.func)
                if dotted:
                    if any(dotted.startswith(p) for p in _BANNED_PREFIXES):
                        self.banned.append((dotted, sub.lineno))
                    if dotted in ("jax.device_get", "device_get"):
                        self.syncs.append((dotted, sub.lineno))
                f = sub.func
                if isinstance(f, ast.Name):
                    self.calls.append(("plain", f.id))
                elif isinstance(f, ast.Attribute):
                    if f.attr == "block_until_ready":
                        self.syncs.append(("block_until_ready", sub.lineno))
                    kind = (
                        "self"
                        if isinstance(f.value, ast.Name) and f.value.id == "self"
                        else "attr"
                    )
                    self.calls.append((kind, f.attr))
            elif isinstance(sub, ast.Global):
                self.globals.append(sub.lineno)
            elif isinstance(sub, (ast.If, ast.While)):
                for n in ast.walk(sub.test):
                    if isinstance(n, ast.Name) and n.id in params:
                        self.param_branches.append((n.id, sub.lineno))
                        break
        # `os.environ[...]` subscript reads (no call)
        for sub in ast.walk(self.node):
            if isinstance(sub, ast.Subscript):
                dotted = _dotted(sub.value)
                if dotted == "os.environ":
                    self.banned.append(("os.environ[]", sub.lineno))


def _collect(relpath: str, tree: ast.Module) -> tuple[list[_Func], list[tuple[str, str, int]]]:
    """(functions, jit-root references) for one module. A root reference
    is (kind, name, line) — the first argument of a jit/pmap/shard_map
    call when it is a plain name or a self-attribute."""
    funcs: list[_Func] = []
    roots: list[tuple[str, str, int]] = []

    def walk(body: list[ast.stmt], cls: str | None) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.append(_Func(relpath, cls, node))
                walk(node.body, cls)
            elif isinstance(node, ast.ClassDef):
                walk(node.body, node.name)
            elif isinstance(node, (ast.If, ast.Try, ast.With, ast.For, ast.While)):
                inner: list[ast.stmt] = list(getattr(node, "body", []))
                inner += list(getattr(node, "orelse", []))
                inner += list(getattr(node, "finalbody", []))
                for h in getattr(node, "handlers", []):
                    inner += h.body
                walk(inner, cls)

    walk(tree.body, None)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = (
            node.func.attr
            if isinstance(node.func, ast.Attribute)
            else node.func.id
            if isinstance(node.func, ast.Name)
            else None
        )
        if fname not in _JIT_WRAPPERS or not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Name):
            roots.append(("plain", arg.id, node.lineno))
        elif (
            isinstance(arg, ast.Attribute)
            and isinstance(arg.value, ast.Name)
            and arg.value.id == "self"
        ):
            roots.append(("self", arg.attr, node.lineno))
    return funcs, roots


def check(root: str | Path, package: str = "policy_server_tpu") -> list[Finding]:
    root = Path(root)
    all_funcs: list[_Func] = []
    root_refs: list[tuple[str, str, str]] = []  # (relpath, kind, name)
    for path in iter_py_files(root, package):
        relpath = str(path.relative_to(root))
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:  # pragma: no cover
            continue
        funcs, roots = _collect(relpath, tree)
        all_funcs.extend(funcs)
        for kind, name, _line in roots:
            root_refs.append((relpath, kind, name))

    by_name: dict[str, list[_Func]] = {}
    for f in all_funcs:
        by_name.setdefault(f.name, []).append(f)

    def resolve(caller_rel: str, caller_cls: str | None, kind: str, name: str) -> _Func | None:
        return resolve_callee(
            by_name.get(name, []),
            caller_rel,
            caller_cls,
            kind,
            module_key=lambda c: c.relpath,
            cls_of=lambda c: c.cls,
        )

    # roots: resolve references; jnp/lax calls inside roots resolve to
    # nothing (library), so traversal stays inside the package
    root_funcs: list[_Func] = []
    for relpath, kind, name in root_refs:
        # root refs may come from any class in the module; try module-level
        # and every class
        cands = [f for f in by_name.get(name, []) if f.relpath == relpath]
        if not cands:
            cands = by_name.get(name, [])
        if cands:
            root_funcs.append(cands[0])

    reachable: dict[str, _Func] = {}
    frontier = list(root_funcs)
    while frontier:
        f = frontier.pop()
        if f.key in reachable:
            continue
        reachable[f.key] = f
        for kind, name in f.calls:
            callee = resolve(f.relpath, f.cls, kind, name)
            if callee is not None and callee.key not in reachable:
                frontier.append(callee)

    findings: list[Finding] = []
    root_keys = {f.key for f in root_funcs}
    for f in reachable.values():
        qual = f"{(f.cls + '.') if f.cls else ''}{f.name}"
        for dotted, line in f.banned:
            findings.append(
                Finding(
                    "tracepurity", "TP01", f.relpath, line,
                    f"{qual}:{dotted}",
                    f"'{dotted}' called in jit-traced code ({qual}): the "
                    "value freezes at trace time",
                )
            )
        for line in f.globals:
            findings.append(
                Finding(
                    "tracepurity", "TP04", f.relpath, line,
                    f"{qual}:global",
                    f"global mutation in jit-traced code ({qual})",
                )
            )
        if f.key in root_keys:
            for pname, line in f.param_branches:
                findings.append(
                    Finding(
                        "tracepurity", "TP02", f.relpath, line,
                        f"{qual}:{pname}",
                        f"Python branch on traced parameter '{pname}' in "
                        f"jit root {qual}: recompile hazard (use jnp.where/"
                        "lax.cond)",
                    )
                )

    # TP03 is package-wide, reachable or not
    for f in all_funcs:
        if f.name in _SYNC_CHOKE_POINTS:
            continue
        qual = f"{(f.cls + '.') if f.cls else ''}{f.name}"
        for what, line in f.syncs:
            findings.append(
                Finding(
                    "tracepurity", "TP03", f.relpath, line,
                    f"{qual}:{what}",
                    f"device sync '{what}' outside the _device_fetch/"
                    f"_device_call choke points (in {qual}): bypasses "
                    "failpoints and the circuit breaker",
                )
            )
    return findings
