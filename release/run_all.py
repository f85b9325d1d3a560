"""Run the release/perf suite (release_tests.yaml) and enforce criteria.

Reference-equivalent of the release-test runner over
release/release_tests.yaml success-criteria (SURVEY §4.5), with teeth:

  * every entry's `criteria` (or `smoke_criteria` under --smoke) is a map
    of metric -> expression (">=N", ">N", "<N", "<=N", "==N");
  * results append to release_history.jsonl (one run per line) so
    regressions are visible across rounds;
  * the process exits NONZERO when any benchmark errors or any criterion
    fails — a deliberately slowed run fails the suite.

Usage: python release/run_all.py [--smoke] [--only NAME]
"""

import json
import os
import subprocess
import sys
import time

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # direct invocation: the script dir, not the
    sys.path.insert(0, REPO)  # repo root, lands on sys.path


def _check(value, expr) -> bool:
    expr = str(expr).strip()
    for op in (">=", "<=", "==", ">", "<"):
        if expr.startswith(op):
            bound = float(expr[len(op):])
            if op == ">=":
                return value >= bound
            if op == "<=":
                return value <= bound
            if op == "==":
                return value == bound
            if op == ">":
                return value > bound
            return value < bound
    raise ValueError(f"bad criterion expression {expr!r}")


def _evaluate(entry: dict, result: dict, smoke: bool) -> list:
    """Returns failure messages (empty = pass)."""
    if "error" in result:
        return [f"benchmark errored: {result['error'][:500]}"]
    criteria = entry.get("criteria", {}) or {}
    if smoke and entry.get("smoke_criteria") is not None:
        criteria = entry["smoke_criteria"] or {}
    failures = []
    for metric, expr in criteria.items():
        if metric == "max_wall_s":
            value = result.get("wall_s")
            if value is not None and value > float(expr):
                failures.append(f"wall_s {value:.0f} > {expr}")
            continue
        value = result.get(metric)
        if value is None:
            failures.append(f"metric {metric!r} missing from output")
        elif not _check(float(value), expr):
            failures.append(f"{metric}={value} fails {expr!r}")
    return failures


def _run_entry(entry: dict, env: dict) -> dict:
    script = entry["script"]
    start = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, script)]
            + list(entry.get("args", [])),
            capture_output=True, text=True,
            timeout=entry.get("timeout_s", 3600), cwd=REPO, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"benchmark": entry["name"],
                "error": f"timeout after {entry.get('timeout_s', 3600)}s"}
    line = next(
        (l for l in reversed(proc.stdout.splitlines()) if l.startswith("{")),
        None,
    )
    if proc.returncode != 0 or line is None:
        return {"benchmark": entry["name"],
                "error": (proc.stderr or proc.stdout)[-2000:]}
    result = json.loads(line)
    result.setdefault("benchmark", entry["name"])
    result["wall_s"] = time.monotonic() - start
    return result


def main() -> int:
    smoke = "--smoke" in sys.argv[1:]
    only = None
    if "--only" in sys.argv[1:]:
        only = sys.argv[sys.argv.index("--only") + 1]
    with open(os.path.join(REPO, "release", "release_tests.yaml")) as fh:
        entries = yaml.safe_load(fh)
    env = dict(os.environ)
    # Scripts live in release/ — python puts the SCRIPT dir on sys.path,
    # not the cwd, so the package import needs the repo root explicitly.
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    if smoke:
        env["RAY_TPU_RELEASE_SMOKE"] = "1"

    results, all_failures = [], []
    for entry in entries:
        if only and entry["name"] != only:
            continue
        print(f"== {entry['name']}", file=sys.stderr)
        result = _run_entry(entry, env)
        failures = _evaluate(entry, result, smoke)
        result["passed"] = not failures
        if failures:
            result["failures"] = failures
            all_failures.append((entry["name"], failures))
        results.append(result)
        print(json.dumps(result), file=sys.stderr)

    from ray_tpu._private.atomic_io import atomic_write_json

    atomic_write_json(
        os.path.join(REPO, "release_results.json"), results, indent=2
    )
    # Append-only history: one line per suite run (regression archaeology).
    with open(os.path.join(REPO, "release_history.jsonl"), "a") as fh:
        fh.write(json.dumps({
            "ts": time.time(), "smoke": smoke, "results": results,
        }) + "\n")
    print(json.dumps(results, indent=2))
    if all_failures:
        for name, failures in all_failures:
            print(f"FAIL {name}: {failures}", file=sys.stderr)
        return 1
    print("release suite: PASS", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
