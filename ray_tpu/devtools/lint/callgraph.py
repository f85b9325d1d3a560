"""CFG-lite interprocedural helpers: the module-local call graph, the
whole-program :class:`ProjectGraph`, async reachability, and the
two-pass lockset analysis.

ISSUE 9 shipped the module-local half (per-module functions + callees —
the clang-tidy scoping trade-off). ISSUE 12 adds the whole-program
layer: import resolution across the ``ray_tpu`` package turns every
``from x import f`` / ``import x as m; m.f()`` call into a cross-module
edge, so reachability rules (blocking-in-async, lockset-order,
sync-inside-overlap-window) follow a call from ``stage_runner.py`` into
``overlap.py`` into ``collective.py``. Per-file summaries are
fingerprint-keyed and cached (see :mod:`cache`), so a full-repo lint
only re-extracts files whose content changed.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Iterator

from ray_tpu.devtools.lint.core import call_name


def collect_functions(tree: ast.Module) -> dict[str, ast.AST]:
    """Qualified name -> def node for every function in the module."""
    out: dict[str, ast.AST] = {}

    def visit(node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                out.setdefault(qual, child)
                visit(child, qual + ".")
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}{child.name}.")
            else:
                visit(child, prefix)

    visit(tree, "")
    return out


def _own_statements(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function body, NOT descending into nested defs (their
    bodies execute on *their* call, not this one)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def local_callees(fn: ast.AST, functions: dict[str, ast.AST],
                  owner_class: str | None) -> set[str]:
    """Qualified names of module-local functions this function calls.

    ``self.m()`` / ``cls.m()`` resolve against the owning class;
    ``name()`` resolves to a module-level def of that name.
    """
    out: set[str] = set()
    for node in _own_statements(fn):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if not name:
            continue
        head, _, tail = name.partition(".")
        if head in ("self", "cls") and tail and owner_class:
            cand = f"{owner_class}.{tail}"
            if cand in functions:
                out.add(cand)
        elif name in functions:
            out.add(name)
    return out


def owner_class_of(qual: str) -> str | None:
    """'Cls.method' -> 'Cls'; bare functions -> None."""
    head, _, _tail = qual.rpartition(".")
    return head or None


def async_reachable(functions: dict[str, ast.AST]) -> dict[str, str]:
    """Map qualified-name -> the async entry point it is reachable from.

    Seeds every ``async def``; propagates over module-local *sync* calls
    (an awaited async callee runs on the loop too, but is flagged at its
    own seed). Value is the root async function's qualified name, for
    diagnostics.
    """
    reach: dict[str, str] = {}
    work: list[str] = []
    for qual, node in functions.items():
        if isinstance(node, ast.AsyncFunctionDef):
            reach[qual] = qual
            work.append(qual)
    while work:
        cur = work.pop()
        node = functions[cur]
        for callee in local_callees(node, functions, owner_class_of(cur)):
            if callee in reach:
                continue
            callee_node = functions[callee]
            if isinstance(callee_node, ast.AsyncFunctionDef):
                continue  # its own seed
            reach[callee] = reach[cur]
            work.append(callee)
    return reach


# ---------------------------------------------------------------------------
# Whole-program callgraph (ISSUE 12)
# ---------------------------------------------------------------------------

def module_name(relpath: str) -> str | None:
    """Dotted module name of a repo-relative ``.py`` path.

    ``ray_tpu/util/gang.py`` -> ``ray_tpu.util.gang``;
    ``ray_tpu/data/__init__.py`` -> ``ray_tpu.data``. Top-level scripts
    (``chip_smoke.py``) map to their bare stem.
    """
    if not relpath.endswith(".py"):
        return None
    parts = relpath[:-3].split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) if parts else None


def summarize_module(tree: ast.Module, relpath: str) -> dict:
    """The JSON-serializable per-file summary the ProjectGraph is built
    from — and the unit the fingerprint-keyed cache stores. Everything
    reachability rules need lives here, so a cache hit skips the whole
    extraction walk:

    * ``functions``: qual -> {async, line, calls [(name, line, col)],
      return_calls [names]} over the function's OWN statements;
    * ``imports``: local binding -> ("module", dotted) for
      ``import x [as m]`` / ``from p import submodule``, or
      ("symbol", module, attr) for ``from p.m import f``.
    """
    mod = module_name(relpath) or ""
    package = mod.rsplit(".", 1)[0] if "." in mod else ""
    is_pkg = relpath.endswith("__init__.py")

    imports: dict[str, list] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                bind = alias.asname or alias.name.split(".")[0]
                target = alias.name if alias.asname else \
                    alias.name.split(".")[0]
                imports[bind] = ["module", target]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                # Relative: level 1 is this file's package, each extra
                # level pops one more component.
                base = mod if is_pkg else package
                for _ in range(node.level - 1):
                    base = base.rsplit(".", 1)[0] if "." in base else ""
                src = f"{base}.{node.module}" if node.module else base
            else:
                src = node.module or ""
            for alias in node.names:
                if alias.name == "*":
                    continue
                bind = alias.asname or alias.name
                imports[bind] = ["symbol", src, alias.name]

    functions: dict[str, dict] = {}
    for qual, fn in collect_functions(tree).items():
        calls: list[list] = []
        return_calls: list[str] = []
        for node in _own_statements(fn):
            if isinstance(node, ast.Call):
                name = call_name(node)
                if name:
                    calls.append([name, node.lineno, node.col_offset])
            elif isinstance(node, ast.Return) and \
                    isinstance(node.value, ast.Call):
                name = call_name(node.value)
                if name:
                    return_calls.append(name)
        functions[qual] = {
            "async": isinstance(fn, ast.AsyncFunctionDef),
            "line": getattr(fn, "lineno", 1),
            "calls": calls,
            "return_calls": return_calls,
        }
    return {"module": mod, "functions": functions, "imports": imports}


class ProjectGraph:
    """Whole-program callgraph over every scanned file.

    Function ids are ``(module, qual)`` tuples (rendered
    ``module:qual`` in messages). Call edges resolve through each
    module's import bindings, so the graph crosses module boundaries:
    ``from x import f; f()``, ``import x.y as m; m.f()``,
    ``collective.get_group(...)`` after
    ``from ... import collective`` — plus absolute dotted references
    via longest-known-module-prefix. ``self.m()`` stays class-local
    (no type inference, same trade-off as the module-local graph).
    """

    def __init__(self, root: str = ""):
        self.root = root
        # module -> {"path", "functions", "imports"}
        self.modules: dict[str, dict] = {}
        self.path_of: dict[str, str] = {}      # module -> relpath
        self.module_of: dict[str, str] = {}    # relpath -> module
        self._callee_cache: dict[tuple, list] = {}
        self._async_reach: dict | None = None

    # -- construction ---------------------------------------------------

    def add_summary(self, relpath: str, summary: dict) -> None:
        mod = summary.get("module") or module_name(relpath)
        if not mod:
            return
        self.modules[mod] = summary
        self.path_of[mod] = relpath
        self.module_of[relpath] = mod

    # -- queries --------------------------------------------------------

    def functions(self) -> Iterator[tuple[tuple[str, str], dict]]:
        for mod, summary in self.modules.items():
            for qual, info in summary["functions"].items():
                yield (mod, qual), info

    def info(self, fid: tuple[str, str]) -> dict | None:
        summary = self.modules.get(fid[0])
        return summary["functions"].get(fid[1]) if summary else None

    def path(self, fid: tuple[str, str]) -> str:
        return self.path_of.get(fid[0], "?")

    @staticmethod
    def render(fid: tuple[str, str]) -> str:
        return f"{fid[0]}:{fid[1]}"

    def _lookup(self, mod: str, name: str):
        """Resolve dotted ``name`` inside module ``mod`` — a function
        qual, or a re-exported submodule attribute."""
        summary = self.modules.get(mod)
        if summary is None:
            return None
        if name in summary["functions"]:
            return (mod, name)
        # one level of module re-export: from pkg import submod
        head, _, tail = name.partition(".")
        bound = summary["imports"].get(head)
        if bound and tail:
            if bound[0] == "module":
                return self._lookup(bound[1], tail)
            if bound[0] == "symbol" and \
                    f"{bound[1]}.{bound[2]}" in self.modules:
                return self._lookup(f"{bound[1]}.{bound[2]}", tail)
        return None

    def resolve_call(
        self, mod: str, owner_class: str | None, name: str
    ):
        """Raw dotted call name -> fid, or None (builtin / foreign /
        dynamic receiver)."""
        summary = self.modules.get(mod)
        if summary is None or not name:
            return None
        head, _, tail = name.partition(".")
        if head in ("self", "cls"):
            if tail and owner_class:
                cand = f"{owner_class}.{tail}"
                if cand in summary["functions"]:
                    return (mod, cand)
            return None
        if name in summary["functions"]:        # module-local
            return (mod, name)
        bound = summary["imports"].get(head)
        if bound is not None:
            if bound[0] == "module":
                target = self._lookup(bound[1], tail) if tail \
                    else None
                if target:
                    return target
            else:  # symbol
                src, attr = bound[1], bound[2]
                full = f"{attr}.{tail}" if tail else attr
                target = self._lookup(src, full)
                if target:
                    return target
                # the imported symbol may itself be a module
                if f"{src}.{attr}" in self.modules and tail:
                    return self._lookup(f"{src}.{attr}", tail)
        # absolute dotted reference: longest known-module prefix
        parts = name.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            prefix = ".".join(parts[:cut])
            if prefix in self.modules:
                return self._lookup(prefix, ".".join(parts[cut:]))
        return None

    def callees(self, fid: tuple[str, str]) -> list[tuple[str, str]]:
        cached = self._callee_cache.get(fid)
        if cached is not None:
            return cached
        info = self.info(fid)
        out: list[tuple[str, str]] = []
        if info:
            owner = owner_class_of(fid[1])
            seen: set = set()
            for name, _line, _col in info["calls"]:
                target = self.resolve_call(fid[0], owner, name)
                if target and target != fid and target not in seen:
                    seen.add(target)
                    out.append(target)
        self._callee_cache[fid] = out
        return out

    def async_reachable(self) -> dict:
        """fid -> the async root fid it is reachable from, across every
        module (the whole-program version of :func:`async_reachable`)."""
        if self._async_reach is not None:
            return self._async_reach
        reach: dict = {}
        work: list = []
        for fid, info in self.functions():
            if info["async"]:
                reach[fid] = fid
                work.append(fid)
        while work:
            cur = work.pop()
            for callee in self.callees(cur):
                if callee in reach:
                    continue
                info = self.info(callee)
                if info is None or info["async"]:
                    continue  # an async callee is its own seed
                reach[callee] = reach[cur]
                work.append(callee)
        self._async_reach = reach
        return reach

    def returning_closure(self, tails: set[str]) -> set:
        """Fids that (transitively) return the result of a call whose
        name ends in one of ``tails`` — e.g. every helper that forwards
        a ``begin_gradient_sync`` handle to its caller."""
        out: set = set()
        changed = True
        while changed:
            changed = False
            for fid, info in self.functions():
                if fid in out:
                    continue
                owner = owner_class_of(fid[1])
                for name in info["return_calls"]:
                    if name.rsplit(".", 1)[-1] in tails:
                        out.add(fid)
                        changed = True
                        break
                    target = self.resolve_call(fid[0], owner, name)
                    if target in out:
                        out.add(fid)
                        changed = True
                        break
        return out


# ---------------------------------------------------------------------------
# Lockset analysis (two-pass)
# ---------------------------------------------------------------------------

_LOCK_CTORS = {
    "threading.Lock", "threading.RLock", "threading.Condition",
    "asyncio.Lock", "asyncio.Condition",
}


@dataclass
class LockSite:
    lock: str       # canonical lock id, e.g. "Controller.self._lock"
    line: int
    node: ast.AST


@dataclass
class LockOrderEdge:
    first: str
    second: str
    path: str
    line: int       # acquisition site of ``second`` while ``first`` held
    via: str        # human-readable chain, e.g. "A.f -> with a -> with b"


@dataclass
class CallUnderLock:
    """A call made while a lock is held — the raw material for the
    cross-module lock-order pass (resolved through the ProjectGraph)."""
    lock: str       # canonical lock id held at the call
    callee: str     # raw dotted call name (unresolved)
    qual: str       # calling function
    line: int


@dataclass
class ModuleLocks:
    """Pass 1 result: the module's named locks + every ordered pair."""
    locks: set[str] = field(default_factory=set)
    edges: list[LockOrderEdge] = field(default_factory=list)
    # qual -> every lock acquisition inside that function
    acquired: dict[str, list[LockSite]] = field(default_factory=dict)
    calls_under_lock: list[CallUnderLock] = field(default_factory=list)


def _lock_names(tree: ast.Module) -> set[str]:
    """Canonical ids of every variable/attribute assigned a lock ctor.

    ``self._lock = threading.Lock()`` inside class C -> ``C.self._lock``;
    module-level ``_LOCK = threading.Lock()`` -> ``_LOCK``.
    """
    names: set[str] = set()

    def canon(target: ast.AST, cls: str | None) -> str | None:
        try:
            txt = ast.unparse(target)
        except (ValueError, RecursionError):  # unparse of odd targets
            return None
        if cls and txt.startswith("self."):
            return f"{cls}.{txt}"
        if "." in txt and not txt.startswith("self."):
            return None  # foreign-object attr: not ours to track
        return txt if not txt.startswith("self.") else None

    def visit(node: ast.AST, cls: str | None) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                visit(child, child.name)
                continue
            if isinstance(child, ast.Assign):
                val = child.value
                if isinstance(val, ast.Call) and \
                        call_name(val) in _LOCK_CTORS:
                    for tgt in child.targets:
                        c = canon(tgt, cls)
                        if c:
                            names.add(c)
            visit(child, cls)

    visit(tree, None)
    return names


def _as_lock(expr: ast.AST, cls: str | None, locks: set[str]) -> str | None:
    """Resolve a with-item / .acquire() receiver to a canonical lock id."""
    try:
        txt = ast.unparse(expr)
    except (ValueError, RecursionError):
        return None
    if cls and txt.startswith("self."):
        cand = f"{cls}.{txt}"
        return cand if cand in locks else None
    return txt if txt in locks else None


def analyze_locks(tree: ast.Module, path: str) -> ModuleLocks:
    """Two-pass lockset: (1) find lock objects and record, per function,
    the ordered pairs of nested acquisitions — including one level of
    same-class calls made while a lock is held; (2) callers diff the
    edge set for inconsistent orderings (see the lockset-order rule).
    """
    result = ModuleLocks(locks=_lock_names(tree))
    if not result.locks:
        return result
    functions = collect_functions(tree)

    # Locks acquired anywhere inside each function (for call propagation).
    acquired_in: dict[str, list[LockSite]] = {}
    for qual, fn in functions.items():
        cls = owner_class_of(qual)
        sites: list[LockSite] = []
        for node in _own_statements(fn):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    lock = _as_lock(item.context_expr, cls, result.locks)
                    if lock:
                        sites.append(LockSite(lock, node.lineno, node))
            elif isinstance(node, ast.Call) and \
                    call_name(node).endswith(".acquire"):
                recv = node.func.value  # type: ignore[attr-defined]
                lock = _as_lock(recv, cls, result.locks)
                if lock:
                    sites.append(LockSite(lock, node.lineno, node))
        acquired_in[qual] = sites
    result.acquired = acquired_in

    def walk_holding(node: ast.AST, held: list[str], qual: str,
                     cls: str | None) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, (ast.With, ast.AsyncWith)):
                inner = [
                    _as_lock(i.context_expr, cls, result.locks)
                    for i in child.items
                ]
                inner = [l for l in inner if l]
                for lock in inner:
                    for h in held:
                        if h != lock:
                            result.edges.append(LockOrderEdge(
                                h, lock, path, child.lineno,
                                via=f"{qual}: with {h} -> with {lock}",
                            ))
                walk_holding(child, held + inner, qual, cls)
                continue
            if isinstance(child, ast.Call) and held:
                name = call_name(child)
                if name and not name.endswith((".acquire", ".release")):
                    for h in held:
                        result.calls_under_lock.append(
                            CallUnderLock(h, name, qual, child.lineno)
                        )
                head, _, tail = name.partition(".")
                callee = None
                if head in ("self", "cls") and tail and cls and \
                        f"{cls}.{tail}" in functions:
                    callee = f"{cls}.{tail}"
                elif name in functions:
                    callee = name
                if callee:
                    for site in acquired_in.get(callee, ()):
                        for h in held:
                            if h != site.lock:
                                result.edges.append(LockOrderEdge(
                                    h, site.lock, path, site.line,
                                    via=(f"{qual}: holds {h}, calls "
                                         f"{callee} which takes "
                                         f"{site.lock}"),
                                ))
            walk_holding(child, held, qual, cls)

    for qual, fn in functions.items():
        walk_holding(fn, [], qual, owner_class_of(qual))
    return result
