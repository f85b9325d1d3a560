"""schedule-deadlock: run the pipeline schedule validator at lint time
over every (S, M, v) grid the repo declares, so a bad schedule config
fails ``ray_tpu lint`` instead of hanging a gang at 3am.

Grid sources:

* literal call sites of ``schedule_1f1b`` / ``schedule_interleaved_1f1b``
  in scanned Python (``ray_tpu/``, ``release/*.py``, tests) — argument
  names resolve through same-function literal assignments
  (``num_stages, microbatches, virtual = 2, 8, 2``) and literal
  ``for s in (2, 4):`` loop iterables, cartesian-product style;
* structured ``schedule_grids:`` declarations on entries in
  ``release/release_tests.yaml`` — either ``{stages, microbatches,
  virtual}`` shapes or explicit per-rank ``ops`` streams for
  simulation fixtures.

Each unique grid is expanded with the REAL schedule generator and
tick-simulated by the REAL ``validate_schedule`` (no reimplementation
to drift); a raise becomes a finding at the declaring site. Certified
grids are recorded on the ProjectGraph for ``ray_tpu lint
--comm-graph`` to report.
"""

from __future__ import annotations

import ast
import os

from ray_tpu.devtools.lint.callgraph import _own_statements
from ray_tpu.devtools.lint.core import (
    FileContext,
    Finding,
    Rule,
    Severity,
    register_rule,
)

_SCHEDULE_FNS = {"schedule_1f1b", "schedule_interleaved_1f1b"}
# Simulation cost ceiling: S ranks x M*v ops each; grids above this are
# configs no release entry ships and not worth lint wall time.
_MAX_OPS = 4096
_MAX_COMBOS = 64


def validate_grid(stages: int, microbatches: int,
                  virtual: int) -> str | None:
    """Expand + simulate one grid with the real validator; returns the
    error text, or None when the grid is deadlock-free."""
    from ray_tpu.parallel.pipeline import (
        schedule_interleaved_1f1b,
        validate_schedule,
    )

    try:
        schedules = [
            schedule_interleaved_1f1b(stages, microbatches, r, virtual)
            for r in range(stages)
        ]
        validate_schedule(schedules, num_virtual=virtual)
    except ValueError as exc:
        return str(exc)
    return None


def _literal_ints(node: ast.AST) -> list[int] | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return [node.value]
    return None


def _scope_env(scope: ast.AST) -> dict[str, list[int]]:
    """name -> possible literal int values, from assignments and
    literal-iterable for loops in one function (or module) scope."""
    env: dict[str, list[int]] = {}

    def bind(name: str, values: list[int]) -> None:
        env.setdefault(name, [])
        for v in values:
            if v not in env[name]:
                env[name].append(v)

    for node in _own_statements(scope):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt, val = node.targets[0], node.value
            if isinstance(tgt, ast.Name):
                ints = _literal_ints(val)
                if ints:
                    bind(tgt.id, ints)
            elif isinstance(tgt, ast.Tuple) and \
                    isinstance(val, ast.Tuple) and \
                    len(tgt.elts) == len(val.elts):
                for t, v in zip(tgt.elts, val.elts):
                    ints = _literal_ints(v)
                    if isinstance(t, ast.Name) and ints:
                        bind(t.id, ints)
        elif isinstance(node, ast.For) and \
                isinstance(node.target, ast.Name) and \
                isinstance(node.iter, (ast.Tuple, ast.List)):
            values: list[int] = []
            for elt in node.iter.elts:
                ints = _literal_ints(elt)
                if not ints:
                    values = []
                    break
                values += ints
            if values:
                bind(node.target.id, values)
    return env


def _resolve(node: ast.AST | None, env: dict[str, list[int]],
             default: list[int] | None = None) -> list[int] | None:
    if node is None:
        return default
    ints = _literal_ints(node)
    if ints:
        return ints
    if isinstance(node, ast.Name):
        return env.get(node.id)
    return None


def _grids_from_ctx(ctx: FileContext):
    """(stages, microbatches, virtual, line) combos declared by literal
    schedule calls in one file."""
    env_cache: dict[int, dict] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        tail = fn.attr if isinstance(fn, ast.Attribute) else \
            fn.id if isinstance(fn, ast.Name) else ""
        if tail not in _SCHEDULE_FNS:
            continue
        scope = ctx.enclosing_function(node) or ctx.tree
        env = env_cache.get(id(scope))
        if env is None:
            env = env_cache[id(scope)] = _scope_env(scope)
        args = node.args
        kw = {k.arg: k.value for k in node.keywords}
        s_vals = _resolve(args[0] if args else kw.get("num_stages"), env)
        m_vals = _resolve(
            args[1] if len(args) > 1 else kw.get("num_microbatches"),
            env)
        if tail == "schedule_1f1b":
            v_vals = [1]
        else:
            v_vals = _resolve(
                args[3] if len(args) > 3 else kw.get("num_virtual"),
                env, default=[1])
        if not (s_vals and m_vals and v_vals):
            continue
        combos = [
            (s, m, v)
            for s in s_vals for m in m_vals for v in v_vals
            if 0 < s and 0 < m and 0 < v and s * m * v <= _MAX_OPS
        ]
        for combo in combos[:_MAX_COMBOS]:
            yield (*combo, node.lineno)


def _entry_line(lines: list[str], name: str) -> int:
    for i, text in enumerate(lines, start=1):
        if f"name: {name}" in text:
            return i
    return 1


def _grids_from_yaml(root: str):
    """Structured grid declarations from release_tests.yaml:
    (kind, payload, yaml_relpath, line, entry_name)."""
    relpath = "release/release_tests.yaml"
    path = os.path.join(root, relpath)
    if not os.path.exists(path):
        return
    try:
        import yaml
    except ImportError:
        return
    try:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        entries = yaml.safe_load(text)
    except (OSError, ValueError, yaml.YAMLError):
        return  # run_all.py owns yaml schema errors; not a lint concern
    if not isinstance(entries, list):
        return
    lines = text.splitlines()
    for entry in entries:
        if not isinstance(entry, dict):
            continue
        name = str(entry.get("name", "?"))
        line = _entry_line(lines, name)
        for grid in entry.get("schedule_grids") or ():
            if not isinstance(grid, dict):
                continue
            if "ops" in grid:
                yield ("ops", grid, relpath, line, name)
            elif {"stages", "microbatches"} <= set(grid):
                yield ("shape", grid, relpath, line, name)


@register_rule
class ScheduleDeadlock(Rule):
    name = "schedule-deadlock"
    severity = Severity.ERROR
    description = ("a declared (S, M, v) pipeline grid fails the "
                   "schedule simulator — would deadlock at run time")

    def check_project(self, ctxs: list[FileContext]):
        project = ctxs[0].project if ctxs else None
        certified: list[dict] = []
        verdicts: dict[tuple, str | None] = {}

        def check(s: int, m: int, v: int) -> str | None:
            key = (s, m, v)
            if key not in verdicts:
                verdicts[key] = validate_grid(s, m, v)
            return verdicts[key]

        for ctx in ctxs:
            for s, m, v, line in _grids_from_ctx(ctx):
                error = check(s, m, v)
                certified.append({
                    "stages": s, "microbatches": m, "virtual": v,
                    "ok": error is None,
                    "source": f"{ctx.path}:{line}",
                })
                if error is not None:
                    yield Finding(
                        rule=self.name, path=ctx.path, line=line,
                        col=1, severity=self.severity,
                        message=(
                            f"schedule grid S={s} M={m} v={v} fails "
                            f"validation: {error}"
                        ),
                    )

        root = project.root if project is not None else ""
        if root:
            from ray_tpu.parallel.pipeline import validate_schedule

            for kind, grid, relpath, line, name in _grids_from_yaml(
                    root):
                if kind == "shape":
                    s = int(grid["stages"])
                    m = int(grid["microbatches"])
                    v = int(grid.get("virtual", 1))
                    if s * m * v > _MAX_OPS:
                        continue
                    error = check(s, m, v)
                    certified.append({
                        "stages": s, "microbatches": m, "virtual": v,
                        "ok": error is None,
                        "source": f"{relpath} ({name})",
                    })
                else:
                    ops = [
                        [tuple(op) for op in rank_ops]
                        for rank_ops in grid["ops"]
                    ]
                    v = int(grid.get("virtual", 1))
                    try:
                        validate_schedule(ops, num_virtual=v)
                        error = None
                    except ValueError as exc:
                        error = str(exc)
                    certified.append({
                        "stages": len(ops), "microbatches": "ops",
                        "virtual": v, "ok": error is None,
                        "source": f"{relpath} ({name})",
                    })
                if error is not None:
                    yield Finding(
                        rule=self.name, path=relpath, line=line,
                        col=1, severity=self.severity,
                        message=(
                            f"schedule_grids entry of '{name}' fails "
                            f"validation: {error}"
                        ),
                    )

        if project is not None:
            # Deduplicated record for `ray_tpu lint --comm-graph`.
            seen: set[tuple] = set()
            project.certified_grids = [
                g for g in certified
                if (key := (g["stages"], g["microbatches"],
                            g["virtual"])) not in seen
                and not seen.add(key)
            ]
