"""lint_clean release entry — the repo must lint clean, with teeth.

Runs rtlint over the default paths (the ray_tpu package + release/)
against the committed baseline and emits one JSON metrics
line for release/run_all.py:

  * findings_new   — findings not covered by .rtlint-baseline.json
                     (criterion ==0: new hazards cannot ship)
  * stale_baseline — ledger entries nothing matched (criterion ==0:
                     fixed debt must leave the ledger)
  * rule_crashes   — rules that died on some file (criterion ==0: a
                     crashing analyzer is a false-negative storm)
  * rules_active   — loaded rule count (criterion >=10: the ISSUE-9
                     framework rules plus the ISSUE-12 protocol
                     verifiers all registered)
  * files_scanned  — coverage sanity floor
  * comm_sites     — communication sites the commgraph extracted
                     (criterion >=40: the protocol rules actually saw
                     the training/collective surface, not an empty
                     graph trivially passing)
"""

import json
import sys


def main() -> int:
    from ray_tpu.devtools.lint.baseline import DEFAULT_BASELINE, Baseline
    from ray_tpu.devtools.lint.runner import (
        default_paths,
        repo_root,
        run_paths,
    )

    root = repo_root()
    baseline = Baseline.load(f"{root}/{DEFAULT_BASELINE}")
    result = run_paths(default_paths(root), root=root, baseline=baseline)
    for f in result.findings:
        print(f"NEW {f.rule} {f.path}:{f.line} {f.message}",
              file=sys.stderr)
    for e in result.stale:
        print(f"STALE {e.get('rule')} {e.get('path')} {e.get('fingerprint')}",
              file=sys.stderr)
    print(json.dumps({
        "benchmark": "lint_clean",
        "findings_new": len(result.findings),
        "findings_baselined": len(result.baselined),
        "stale_baseline": len(result.stale),
        "suppressed_inline": result.suppressed,
        "rule_crashes": result.stats["rule_crashes"],
        "rules_active": result.stats["rules"],
        "files_scanned": result.stats["files"],
        "comm_sites": result.stats["comm_sites"],
        "cache_hits": result.stats["cache_hits"],
        "wall_s": result.stats["wall_s"],
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
