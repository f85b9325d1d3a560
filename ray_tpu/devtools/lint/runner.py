"""rtlint runner: file discovery, rule execution, baseline diff, CLI.

Programmatic entry point is :func:`run_paths`; the CLI (`ray_tpu lint`)
is :func:`main`, wired from ``ray_tpu/scripts.py``.

Exit codes: 0 clean (modulo baseline), 1 new findings or stale baseline
entries, 2 usage/internal error. A rule that *crashes* on a file is
itself reported as a finding (`rtlint-crash`) rather than taking the
whole run down — an analyzer that dies on weird-but-valid code is a
false-negative storm, which the `lint_clean` release entry gates.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass, field

from ray_tpu.devtools.lint.baseline import DEFAULT_BASELINE, Baseline
from ray_tpu.devtools.lint.cache import (
    DEFAULT_CACHE,
    SummaryCache,
    fingerprint_source,
)
from ray_tpu.devtools.lint.core import (
    FileContext,
    Finding,
    Severity,
    all_rules,
    assign_fingerprints,
)
from ray_tpu.devtools.lint.output import RENDERERS

_SKIP_DIRS = {"__pycache__", ".git", "node_modules", ".eggs", "build"}


def repo_root() -> str:
    """Parent of the installed ray_tpu package — the repo checkout."""
    import ray_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(
        ray_tpu.__file__)))


def iter_py_files(paths: list[str]) -> list[str]:
    out: list[str] = []
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(
                d for d in dirnames if d not in _SKIP_DIRS
            )
            for name in sorted(filenames):
                if name.endswith(".py"):
                    out.append(os.path.join(dirpath, name))
    return out


@dataclass
class RunResult:
    findings: list[Finding] = field(default_factory=list)   # not baselined
    baselined: list[Finding] = field(default_factory=list)
    stale: list[dict] = field(default_factory=list)
    suppressed: int = 0
    stats: dict = field(default_factory=dict)
    project: object = None          # callgraph.ProjectGraph of the run

    @property
    def exit_code(self) -> int:
        return 1 if (self.findings or self.stale) else 0


def build_project(
    ctxs: list[FileContext], root: str, cache: SummaryCache
):
    """Whole-program layer: per-file summaries (callgraph + comm sites)
    through the fingerprint cache, assembled into one ProjectGraph that
    every FileContext shares."""
    from ray_tpu.devtools.analysis import commgraph
    from ray_tpu.devtools.lint import callgraph

    project = callgraph.ProjectGraph(root=root)
    comm_sites: list[dict] = []
    for ctx in ctxs:
        ctx.fingerprint = fingerprint_source(ctx.source)
        ctx.module = callgraph.module_name(ctx.path) or ""
        summary = cache.get(ctx.path, ctx.fingerprint)
        if summary is None:
            summary = {
                "callgraph": callgraph.summarize_module(
                    ctx.tree, ctx.path),
                "comm": commgraph.extract_sites(ctx.tree, ctx.path),
            }
            cache.put(ctx.path, ctx.fingerprint, summary)
        project.add_summary(ctx.path, summary["callgraph"])
        comm_sites.extend(summary["comm"])
    project.comm_sites = comm_sites
    for ctx in ctxs:
        ctx.project = project
    return project


def run_paths(
    paths: list[str],
    *,
    root: str | None = None,
    select: set[str] | None = None,
    disable: set[str] | None = None,
    baseline: Baseline | None = None,
    cache_path: str | None = None,
    use_cache: bool = True,
) -> RunResult:
    root = root or repo_root()
    rule_classes = all_rules()
    active = {
        name: cls
        for name, cls in rule_classes.items()
        if (select is None or name in select)
        and (disable is None or name not in disable)
    }
    start = time.perf_counter()
    ctxs: list[FileContext] = []
    parse_errors: list[Finding] = []
    for abspath in iter_py_files(paths):
        rel = os.path.relpath(abspath, root).replace(os.sep, "/")
        try:
            with open(abspath, encoding="utf-8", errors="replace") as fh:
                source = fh.read()
            ctxs.append(FileContext.parse(rel, source))
        except SyntaxError as exc:
            parse_errors.append(Finding(
                rule="rtlint-parse", path=rel,
                line=exc.lineno or 1, col=(exc.offset or 0) + 1,
                severity=Severity.ERROR,
                message=f"file does not parse: {exc.msg}",
            ))

    if use_cache and cache_path is None:
        cache_path = os.path.join(root, DEFAULT_CACHE)
    cache = SummaryCache.load(cache_path if use_cache else None)
    project = build_project(ctxs, root, cache)
    cache.save()

    raw: list[Finding] = list(parse_errors)
    crashes = 0
    for name, cls in sorted(active.items()):
        rule = cls()
        try:
            raw.extend(rule.check_project(ctxs))
        except Exception as exc:  # one broken rule must not kill the gate
            crashes += 1
            raw.append(Finding(
                rule="rtlint-crash", path="<analyzer>", line=1, col=1,
                severity=Severity.ERROR,
                message=f"rule {name} crashed: {type(exc).__name__}: {exc}",
            ))

    # Inline suppressions.
    by_path = {c.path: c for c in ctxs}
    kept: list[Finding] = []
    suppressed = 0
    for f in raw:
        ctx = by_path.get(f.path)
        if ctx is not None and ctx.suppressions.is_suppressed(
                f.rule, f.line):
            suppressed += 1
            continue
        kept.append(f)

    assign_fingerprints(kept, {c.path: c.lines for c in ctxs})

    baseline = baseline or Baseline()
    new, matched, stale = baseline.split(kept)
    stats = {
        "files": len(ctxs),
        "rules": len(active),
        "rule_names": sorted(active),
        "suppressed_inline": suppressed,
        "rule_crashes": crashes,
        "cache_hits": cache.hits,
        "cache_misses": cache.misses,
        "comm_sites": len(getattr(project, "comm_sites", ())),
        "wall_s": round(time.perf_counter() - start, 3),
    }
    return RunResult(findings=new, baselined=matched, stale=stale,
                     suppressed=suppressed, stats=stats,
                     project=project)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def add_lint_arguments(p: argparse.ArgumentParser) -> None:
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: the ray_tpu "
                        "package + release/ in this checkout)")
    p.add_argument("--format", choices=sorted(RENDERERS),
                   default="human")
    p.add_argument("--out", default=None,
                   help="write the report to a file (atomic) instead "
                        "of stdout")
    p.add_argument("--baseline", default=None,
                   help=f"baseline file (default: <repo>/"
                        f"{DEFAULT_BASELINE} when present)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding, ignoring the baseline")
    p.add_argument("--write-baseline", action="store_true",
                   help="accept all current findings into the baseline "
                        "(existing justifications are preserved; new "
                        "entries get a TODO you must fill in)")
    p.add_argument("--prune-baseline", action="store_true",
                   help="rewrite the baseline keeping only entries that "
                        "still match (justifications preserved); stale "
                        "entries stop failing the run")
    p.add_argument("--select", default=None,
                   help="comma-separated rule names to run exclusively")
    p.add_argument("--disable", default=None,
                   help="comma-separated rule names to skip")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--comm-graph", action="store_true",
                   help="print the communication-protocol certification "
                        "summary (channel graph + schedule grids)")
    p.add_argument("--comm-graph-out", default=None, metavar="FILE",
                   help="export the channel graph (.dot or .json by "
                        "extension); implies --comm-graph")
    p.add_argument("--no-cache", action="store_true",
                   help="skip the incremental summary cache "
                        "(.rtlint-cache.json)")


def default_paths(root: str) -> list[str]:
    paths = [os.path.join(root, "ray_tpu")]
    release = os.path.join(root, "release")
    if os.path.exists(release):
        paths.append(release)
    return paths


def _emit_comm_graph(result: RunResult, out: str | None) -> None:
    """Print the protocol-certification summary and optionally export
    the channel graph (DOT for graphviz, JSON otherwise)."""
    from ray_tpu.devtools.analysis.commgraph import graph_from_project

    graph = graph_from_project(result.project)
    channels = graph.channels()
    unmatched = [c for c in channels if not c.recvs]
    orphans = graph.unmatched_recvs()
    print(f"comm-graph: {len(graph.sites)} sites "
          f"({len(graph.sends)} send / {len(graph.recvs)} recv), "
          f"{len(channels)} channels, "
          f"{len(unmatched)} unmatched send(s), "
          f"{len(orphans)} orphan recv(s)")
    grids = getattr(result.project, "certified_grids", None)
    if grids is None:
        print("comm-graph: schedule grids not checked "
              "(schedule-deadlock rule disabled)")
    else:
        ok = [g for g in grids if g["ok"]]
        bad = [g for g in grids if not g["ok"]]
        desc = ", ".join(
            f"S={g['stages']}xM={g['microbatches']}xv={g['virtual']}"
            for g in ok
        ) or "none declared"
        print(f"comm-graph: {len(ok)} schedule grid(s) certified "
              f"deadlock-free ({desc})"
              + (f"; {len(bad)} FAILED" if bad else ""))
    if out:
        from ray_tpu._private.atomic_io import atomic_write_text

        text = graph.to_dot() if out.endswith(".dot") else \
            json.dumps(graph.to_json(), indent=2) + "\n"
        atomic_write_text(out, text)
        print(f"comm-graph: exported to {out}")


def cmd_lint(args: argparse.Namespace) -> int:
    root = repo_root()
    if args.list_rules:
        for name, cls in sorted(all_rules().items()):
            print(f"{name:28s} {cls.severity:8s} {cls.description}")
        return 0
    paths = [os.path.abspath(p) for p in args.paths] or \
        default_paths(root)
    for p in paths:
        if not os.path.exists(p):
            print(f"rtlint: no such path: {p}", file=sys.stderr)
            return 2

    baseline_path = args.baseline or os.path.join(root, DEFAULT_BASELINE)
    baseline = Baseline() if args.no_baseline else \
        Baseline.load(baseline_path)

    select = set(args.select.split(",")) if args.select else None
    disable = set(args.disable.split(",")) if args.disable else None
    unknown = (set() if select is None else select - set(all_rules())) \
        | (set() if disable is None else disable - set(all_rules()))
    if unknown:
        print(f"rtlint: unknown rule(s): {', '.join(sorted(unknown))}",
              file=sys.stderr)
        return 2

    result = run_paths(paths, root=root, select=select, disable=disable,
                       baseline=baseline,
                       use_cache=not getattr(args, "no_cache", False))

    if args.prune_baseline:
        kept = result.baselined
        removed = len(result.stale)
        baseline.save(baseline_path, kept)
        print(f"rtlint: baseline pruned — {removed} stale entr"
              f"{'y' if removed == 1 else 'ies'} removed, "
              f"{len(kept)} kept at {baseline_path}")
        result.stale = []

    if args.comm_graph or args.comm_graph_out:
        _emit_comm_graph(result, args.comm_graph_out)

    if args.write_baseline:
        accepted = result.findings + result.baselined
        baseline.save(baseline_path, accepted)
        print(f"rtlint: baseline written to {baseline_path} "
              f"({len(accepted)} entries) — fill in every TODO "
              f"justification before committing")
        return 0

    text = RENDERERS[args.format](
        result.findings, result.baselined, result.stale, result.stats
    )
    if args.out:
        from ray_tpu._private.atomic_io import atomic_write_text

        atomic_write_text(args.out, text + "\n")
        if args.format == "human" or result.findings or result.stale:
            print(f"rtlint: report written to {args.out} "
                  f"({len(result.findings)} new finding(s))")
    else:
        print(text)
    return result.exit_code


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="rtlint")
    add_lint_arguments(parser)
    return cmd_lint(parser.parse_args(argv))


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
