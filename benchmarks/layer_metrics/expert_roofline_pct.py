"""Layer: Kernels. The least time the chips could take for what the expert
matmuls of a step need (harness/moe_flops.experts_needed: ``tokens x k``
rows through three matrices, three passes, operations and bytes from
shapes) over ``expert_ms``. ``expert_ms`` also holds ``_silu_mul`` and its
recompute, which the need does not grant."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import expert_ms


def read(run):
    took_ms = expert_ms.read(run)
    needed = run["facts"].get("kernel_needed", {}).get("experts")
    if not took_ms or not needed:
        return None
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
