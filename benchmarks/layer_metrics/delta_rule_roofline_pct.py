"""Layer: Kernels. The least time the chips could take for what the delta
rule of a step needs (harness/hybrid_flops.delta_rule_needed: the
recurrence's own operations and q, k, v, the gates, ``o`` and their
gradients moved once; memory-bound on a v5e) over ``delta_rule_ms``."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import delta_rule_ms


def read(run):
    took_ms = delta_rule_ms.read(run)
    needed = run["facts"].get("kernel_needed", {}).get("delta_rule")
    if not took_ms or not needed:
        return None
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
